"""The allocate solver: Volcano's hot loop as one jitted XLA program.

Replaces the namespace->queue->job->task object loop of
``pkg/scheduler/actions/allocate/allocate.go:40-250`` (predicate fan-out,
score fan-out, best-node selection, capacity update, gang commit/discard)
with a single sequential scan over pre-ordered tasks carrying dense cluster
state.  Semantics preserved per task step:

- predicate  = bitset predicates evaluated in-loop against the node tables
  (selector / required node-affinity / taints / ready — the predicates
  plugin, predicates.go:144-293) AND InitResreq fits FutureIdle
  (allocate.go:98-105) AND pod-count AND host-port AND inter-pod
  (anti)affinity on live per-(term, domain) count tensors
  (predicates.go:111-136,272-291)
- score      = additive scorers on current node state (allocate.go:202)
  plus preferred node affinity and soft pod-affinity/spread terms
- selection  = masked argmax (SelectBestNode; first-index tie-break instead
  of random-among-max)
- fits Idle  -> allocate: idle/queue/pod-count/ports updated (stmt.Allocate)
- else       -> pipeline: FutureIdle reduced, effects NOT rolled back on
  discard (ssn.Pipeline is session-level; allocate.go:224-232)
- a task with no feasible node aborts the remaining tasks of its job
  (allocate.go:189-193 break)
- gang       = job-boundary rollback: a job that never reaches ready
  (ready_base + newly_allocated >= min_available) has all its allocations
  undone (stmt.Discard, allocate.go:241-245).  Rollback replays the job's
  own task rows backwards (an undo log over at most the job's size) instead
  of checkpointing full [N, R] arrays — the difference between O(job) work
  on the rare discard and O(N*R) copies on EVERY step.

Nothing of size [P, N] is ever materialized: predicates and scores for one
task row are computed in-loop from [N, *]-sized tables, so the solver
scales to 50k nodes x 500k tasks (BASELINE config 5) where a dense mask
alone would be 2.5e10 entries.

Deviations from the reference (documented):
- the reference re-picks the next <namespace, queue, job> after every job
  using *live* DRF/share orderings; the fused solver processes jobs in the
  order fixed at encode time.  The host action can run the solver in
  multiple rounds with re-sorted order to recover the dynamic behavior
  (actions/allocate.py).
- tie-break is deterministic (lowest node index) instead of the reference's
  random-among-max (scheduler_helper.go:201-212).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..arrays.affinity import AffinityArgs
from .resreq import less_equal
from .scoring import ScoreWeights, node_score

# A NumPy scalar, not jnp.float32(...): a jnp call at import time
# initialises the backend, so merely importing the package would take
# the chip (and a process that must stay off it could not say so first).
NEG = np.float32(-3.0e38)


class SolveNodes(NamedTuple):
    """Node-side solver inputs (all leading dim N)."""

    idle: jnp.ndarray  # [N, R]
    allocatable: jnp.ndarray  # [N, R]
    releasing: jnp.ndarray  # [N, R]
    pipelined: jnp.ndarray  # [N, R]
    ntasks: jnp.ndarray  # [N] int32
    max_tasks: jnp.ndarray  # [N] int32 (0 = unlimited)
    ports: jnp.ndarray  # [N, PW] uint32
    ready: jnp.ndarray  # [N] bool (ready & schedulable & real)
    label_bits: jnp.ndarray  # [N, LW] uint32
    taint_bits: jnp.ndarray  # [N, TW] uint32


class SolveTasks(NamedTuple):
    """Task-side solver inputs (leading dim P, job-contiguous order)."""

    req: jnp.ndarray  # [P, R]
    init_req: jnp.ndarray  # [P, R]
    job: jnp.ndarray  # [P] int32
    real: jnp.ndarray  # [P] bool
    ports: jnp.ndarray  # [P, PW] uint32
    sel_bits: jnp.ndarray  # [P, LW] node-selector label pairs (AND)
    aff_bits: jnp.ndarray  # [P, A, LW] required node-affinity alternatives
    aff_terms: jnp.ndarray  # [P] int32 number of alternatives (0 = none)
    tol_bits: jnp.ndarray  # [P, TW] tolerated taints
    pref_bits: jnp.ndarray  # [P, AP, LW] preferred node-affinity terms
    pref_w: jnp.ndarray  # [P, AP] float32 term scores (pre-normalized *10)


class SolveJobs(NamedTuple):
    queue: jnp.ndarray  # [J] int32
    min_available: jnp.ndarray  # [J] int32
    ready_base: jnp.ndarray  # [J] int32


class SolveQueues(NamedTuple):
    deserved: jnp.ndarray  # [Q, R] (+inf when proportion disabled)
    allocated: jnp.ndarray  # [Q, R] at session open


class AllocState(NamedTuple):
    """Carry of the sequential scan.  Pipeline-side state (pip_*) survives
    gang rollback (session-level Pipeline); allocation-side state is undone
    via the per-task undo log at discard."""

    idle: jnp.ndarray  # [N, R]
    pip_extra: jnp.ndarray  # [N, R] pipelined additions this cycle
    ntasks: jnp.ndarray  # [N]
    pip_ntasks: jnp.ndarray  # [N]
    nports: jnp.ndarray  # [N, PW] uint32
    pip_nports: jnp.ndarray  # [N, PW]
    cnt_alloc: jnp.ndarray  # [E, D] affinity-term counts from allocations
    cnt_pip: jnp.ndarray  # [E, D] affinity-term counts from pipelines
    q_alloc: jnp.ndarray  # [Q, R]
    q_pip: jnp.ndarray  # [Q, R]
    assigned: jnp.ndarray  # [P] node index or -1
    pipelined: jnp.ndarray  # [P] node index or -1
    alloc_cnt: jnp.ndarray  # [J]
    never_ready: jnp.ndarray  # [J] bool
    fit_failed: jnp.ndarray  # [J] bool
    job_start: jnp.ndarray  # scalar int32: first task row of current job
    prev_job: jnp.ndarray  # scalar int32
    job_ready: jnp.ndarray  # scalar bool
    job_skip: jnp.ndarray  # scalar bool (overused-skip OR fit-failure abort)
    job_overskip: jnp.ndarray  # scalar bool: skipped for queue overuse only


class AllocResult(NamedTuple):
    assigned: jnp.ndarray  # [P] committed node index or -1
    pipelined: jnp.ndarray  # [P] pipelined node index or -1
    never_ready: jnp.ndarray  # [J] bool (gang discard happened)
    fit_failed: jnp.ndarray  # [J] bool
    idle: jnp.ndarray  # [N, R] final idle
    q_alloc: jnp.ndarray  # [Q, R] final queue allocated (incl. pipelines)
    iters: jnp.ndarray = None  # [] total attempt iterations (diagnostics)
    # Two-phase wave solve only (ops/wave.py): shortlist-fallback
    # rescore counts by reason — profiles whose candidate shortlist ran
    # dry (exhausted) vs required-(anti)affinity profiles whose live
    # domain landscape drifted from the solve-start counts the
    # shortlist was built on.  None from the sequential solver.
    fb_exhausted: jnp.ndarray = None  # [] int32
    fb_affinity: jnp.ndarray = None  # [] int32
    # Wave solve only: jobs the queue-overuse gate refused (the count of
    # ``job_overskip``), for the cycle record's ``solve`` counts.
    overuse_gated: jnp.ndarray = None  # [] int32
    # ``has_aff`` wave solve only: how often the solve recomputed its
    # count plane (``ops/wave.count_plane``).
    aff_count_reads: jnp.ndarray = None  # [] int32


def _subset(bits_row, table):
    """[..., W] & [N, W] -> [..., N]: row bits all present in table rows."""
    missing = bits_row[..., None, :] & ~table
    return jnp.all(missing == 0, axis=-1)


def solve_inputs(arrays, deserved=None, q_alloc0=None):
    """Build the (nodes, tasks, jobs, queues) solver groups from encoded
    ClusterArrays.  ``deserved`` defaults to +inf (proportion gating off)."""
    import numpy as np

    n, t, j, q = arrays.nodes, arrays.tasks, arrays.jobs, arrays.queues
    Q, R = q.capability.shape
    if deserved is None:
        deserved = np.full((Q, R), 3.0e38, np.float32)
    if q_alloc0 is None:
        q_alloc0 = q.allocated
    return (
        SolveNodes(
            idle=n.idle,
            allocatable=n.allocatable,
            releasing=n.releasing,
            pipelined=n.pipelined,
            ntasks=n.num_tasks,
            max_tasks=n.max_tasks,
            ports=n.port_bits,
            ready=n.ready & n.real,
            label_bits=n.label_bits,
            taint_bits=n.taint_bits,
        ),
        SolveTasks(
            req=t.req,
            init_req=t.init_req,
            job=t.job,
            real=t.real,
            ports=t.port_bits,
            sel_bits=t.sel_bits,
            aff_bits=t.aff_bits,
            aff_terms=t.aff_terms,
            tol_bits=t.tol_bits,
            pref_bits=t.pref_bits,
            pref_w=t.pref_w,
        ),
        SolveJobs(
            queue=j.queue,
            min_available=j.min_available,
            ready_base=j.ready_base,
        ),
        SolveQueues(
            deserved=np.asarray(deserved, np.float32),
            allocated=np.asarray(q_alloc0, np.float32),
        ),
    )


@jax.jit
def solve(
    nodes: SolveNodes,
    tasks: SolveTasks,
    jobs: SolveJobs,
    queues: SolveQueues,
    weights: ScoreWeights,
    eps,  # [R]
    scalar_slot,  # [R]
    aff: AffinityArgs,  # inter-pod affinity/spread count block
    extra_ok=None,  # optional [P, N] bool: custom-plugin predicate verdicts
    extra_score=None,  # optional [P, N] f32: custom-plugin node scores
) -> AllocResult:
    P, _ = tasks.req.shape
    J = jobs.min_available.shape[0]
    A = tasks.aff_bits.shape[1]
    E, _D = aff.cnt0.shape
    cnt0 = aff.cnt0.astype(jnp.int32)
    term_arange = jnp.arange(E)
    node_dom_t = aff.node_dom[:, aff.term_key]  # [N, E]

    state = AllocState(
        idle=nodes.idle,
        pip_extra=jnp.zeros_like(nodes.idle),
        ntasks=nodes.ntasks,
        pip_ntasks=jnp.zeros_like(nodes.ntasks),
        nports=nodes.ports,
        pip_nports=jnp.zeros_like(nodes.ports),
        cnt_alloc=cnt0,
        cnt_pip=jnp.zeros_like(cnt0),
        q_alloc=queues.allocated,
        q_pip=jnp.zeros_like(queues.allocated),
        assigned=jnp.full((P,), -1, jnp.int32),
        pipelined=jnp.full((P,), -1, jnp.int32),
        alloc_cnt=jnp.zeros((J,), jnp.int32),
        never_ready=jnp.zeros((J,), bool),
        fit_failed=jnp.zeros((J,), bool),
        job_start=jnp.int32(0),
        prev_job=jnp.int32(-1),
        job_ready=jnp.bool_(True),
        job_skip=jnp.bool_(True),
        job_overskip=jnp.bool_(True),
    )

    def _undo_job(start, end, pj_c, s: AllocState):
        """Roll back the allocations of job rows [start, end) (stmt.Discard,
        statement.go:324-367).  O(job size), touching only assigned rows."""
        qj = jobs.queue[pj_c]

        def body(u, carry):
            idle, ntasks, nports, cnt_alloc, q_alloc = carry
            n = s.assigned[u]
            did = n >= 0
            n_c = jnp.maximum(n, 0)
            radd = jnp.where(did, tasks.req[u], jnp.zeros_like(tasks.req[u]))
            idle = idle.at[n_c].add(radd)
            ntasks = ntasks.at[n_c].add(jnp.where(did, -1, 0))
            # Port bits were disjoint from pre-existing at allocate time, so
            # AND-NOT is an exact inverse of the OR.
            nports = nports.at[n_c].set(
                jnp.where(did, nports[n_c] & ~tasks.ports[u], nports[n_c])
            )
            dom_u = node_dom_t[n_c]  # [E]
            dec = aff.t_matches[u] & (dom_u >= 0) & did
            cnt_alloc = cnt_alloc.at[
                term_arange, jnp.maximum(dom_u, 0)
            ].add(-dec.astype(jnp.int32))
            q_alloc = q_alloc.at[qj].add(-radd)
            return (idle, ntasks, nports, cnt_alloc, q_alloc)

        return jax.lax.fori_loop(
            start, end, body,
            (s.idle, s.ntasks, s.nports, s.cnt_alloc, s.q_alloc),
        )

    def step(t, s: AllocState) -> AllocState:
        tt = jnp.minimum(t, P - 1)
        is_pad = (t >= P) | ~tasks.real[tt]
        jt = jnp.where(is_pad, jnp.int32(-1), tasks.job[tt])
        jt_c = jnp.maximum(jt, 0)

        # ---- job boundary: finalize previous job, open new one ----------
        new_job = jt != s.prev_job
        # Discard when the previous job never reached ready — including
        # jobs aborted mid-way by a fit failure (Go breaks the task loop,
        # then commit/discard still runs; allocate.go:189-245).  Jobs that
        # were only *skipped* for queue overuse were never processed: no
        # statement existed, so no discard is reported for them.
        discard = new_job & (s.prev_job >= 0) & ~s.job_ready & ~s.job_overskip
        pj_c = jnp.maximum(s.prev_job, 0)

        idle, ntasks, nports, cnt_alloc, q_alloc = jax.lax.cond(
            discard,
            lambda: _undo_job(s.job_start, t, pj_c, s),
            lambda: (s.idle, s.ntasks, s.nports, s.cnt_alloc, s.q_alloc),
        )
        never_ready = s.never_ready.at[pj_c].set(
            s.never_ready[pj_c] | discard
        )

        # New-job bookkeeping: overuse check, base readiness, undo-log start.
        job_start = jnp.where(new_job, t, s.job_start)
        qj = jobs.queue[jt_c]
        q_total = q_alloc[qj] + s.q_pip[qj]
        overused = ~less_equal(q_total, queues.deserved[qj], eps, scalar_slot)
        job_skip = jnp.where(new_job, (jt < 0) | overused, s.job_skip)
        job_overskip = jnp.where(
            new_job, (jt < 0) | overused, s.job_overskip
        )
        job_ready = jnp.where(
            new_job,
            (jt >= 0) & (jobs.ready_base[jt_c] >= jobs.min_available[jt_c]),
            s.job_ready,
        )
        prev_job = jnp.where(new_job, jt, s.prev_job)

        # ---- per-task processing (fully masked) -------------------------
        active = ~is_pad & ~job_skip

        # Static predicates, in-loop from the bitset tables ([N]-sized).
        ok = nodes.ready & _subset(tasks.sel_bits[tt], nodes.label_bits)
        term_ok = _subset(tasks.aff_bits[tt], nodes.label_bits)  # [A, N]
        n_terms = tasks.aff_terms[tt]
        term_real = jnp.arange(A) < n_terms  # [A]
        ok &= jnp.any(term_ok & term_real[:, None], axis=0) | (n_terms == 0)
        untol = nodes.taint_bits & ~tasks.tol_bits[tt][None, :]
        ok &= jnp.all(untol == 0, axis=-1)

        future_idle = idle + nodes.releasing - nodes.pipelined - s.pip_extra
        fit_future = less_equal(
            tasks.init_req[tt][None, :], future_idle, eps, scalar_slot
        )
        total_ntasks = ntasks + s.pip_ntasks
        pods_ok = (nodes.max_tasks <= 0) | (total_ntasks < nodes.max_tasks)
        ports_used = nports | s.pip_nports
        ports_ok = jnp.all(
            (tasks.ports[tt][None, :] & ports_used) == 0, axis=-1
        )

        # Inter-pod affinity/anti-affinity + soft spread on the live counts.
        # cval[N, E]: matching-pod count in each node's domain for each term;
        # -1 domains (node lacks the topology label) read as 0.
        cnt = cnt_alloc + s.cnt_pip  # [E, D]
        cval = cnt[term_arange[None, :], jnp.maximum(node_dom_t, 0)]
        cval = jnp.where(node_dom_t >= 0, cval, 0)
        total = jnp.sum(cnt, axis=-1)  # [E]
        req_a = aff.t_req_aff[tt]  # [E]
        req_n = aff.t_req_anti[tt]
        # Upstream self-match rule: an affinity term with no matching pod
        # anywhere passes iff the incoming pod matches its own selector.
        aff_term_ok = (cval > 0) | ((total == 0) & aff.t_matches[tt])[None, :]
        aff_ok = jnp.all(~req_a[None, :] | aff_term_ok, axis=-1)
        anti_ok = jnp.all(~req_n[None, :] | (cval == 0), axis=-1)

        feasible = ok & fit_future & pods_ok & ports_ok & aff_ok & anti_ok
        if extra_ok is not None:
            # Custom-plugin predicate verdicts (session add_predicate_fn /
            # add_device_mask_fn contributions from out-of-tree plugins).
            feasible &= extra_ok[tt]
        any_feasible = jnp.any(feasible)

        score = node_score(tasks.req[tt], nodes.allocatable, idle, weights)
        if extra_score is not None:
            score = score + extra_score[tt]
        # Preferred node affinity (CalculateNodeAffinityPriority): term
        # scores are pre-normalized to *10 at encode; the weight knob is
        # applied here so config controls it.
        pref_match = _subset(tasks.pref_bits[tt], nodes.label_bits)  # [AP, N]
        score = score + weights.node_affinity_weight * jnp.sum(
            pref_match * tasks.pref_w[tt][:, None], axis=0
        )
        score = score + jnp.sum(
            aff.t_soft[tt][None, :] * cval.astype(jnp.float32), axis=-1
        )
        score = jnp.where(feasible, score, NEG)
        best = jnp.argmax(score).astype(jnp.int32)
        fits_idle = less_equal(tasks.init_req[tt], idle[best], eps, scalar_slot)

        do_alloc = active & any_feasible & fits_idle
        do_pipeline = active & any_feasible & ~fits_idle
        no_node = active & ~any_feasible

        # Allocation-side updates (stmt.Allocate).
        radd = jnp.where(
            do_alloc, tasks.req[tt], jnp.zeros_like(tasks.req[tt])
        )
        idle = idle.at[best].add(-radd)
        ntasks = ntasks.at[best].add(do_alloc.astype(jnp.int32))
        nports = nports.at[best].set(
            jnp.where(do_alloc, nports[best] | tasks.ports[tt], nports[best])
        )
        # Affinity-count update: the placed pod becomes "resident" for every
        # term its labels/job match (predicates plugin Allocate event).
        dom_t = node_dom_t[best]  # [E]
        inc_base = aff.t_matches[tt] & (dom_t >= 0)
        cnt_alloc = cnt_alloc.at[term_arange, jnp.maximum(dom_t, 0)].add(
            (inc_base & do_alloc).astype(jnp.int32)
        )
        q_alloc = q_alloc.at[qj].add(radd)
        assigned = s.assigned.at[tt].set(
            jnp.where(do_alloc, best, s.assigned[tt])
        )
        alloc_cnt = s.alloc_cnt.at[jt_c].add(do_alloc.astype(jnp.int32))
        job_ready = job_ready | (
            do_alloc
            & (jobs.ready_base[jt_c] + alloc_cnt[jt_c]
               >= jobs.min_available[jt_c])
        )

        # Pipeline-side updates (ssn.Pipeline; survive discard).
        padd = jnp.where(
            do_pipeline, tasks.req[tt], jnp.zeros_like(tasks.req[tt])
        )
        pip_extra = s.pip_extra.at[best].add(padd)
        pip_ntasks = s.pip_ntasks.at[best].add(do_pipeline.astype(jnp.int32))
        pip_nports = s.pip_nports.at[best].set(
            jnp.where(
                do_pipeline,
                s.pip_nports[best] | tasks.ports[tt],
                s.pip_nports[best],
            )
        )
        cnt_pip = s.cnt_pip.at[term_arange, jnp.maximum(dom_t, 0)].add(
            (inc_base & do_pipeline).astype(jnp.int32)
        )
        q_pip = s.q_pip.at[qj].add(padd)
        pipelined = s.pipelined.at[tt].set(
            jnp.where(do_pipeline, best, s.pipelined[tt])
        )

        # Fit failure aborts the rest of the job (allocate.go:189-193).
        fit_failed = s.fit_failed.at[jt_c].set(s.fit_failed[jt_c] | no_node)
        job_skip = job_skip | no_node

        return AllocState(
            idle=idle,
            pip_extra=pip_extra,
            ntasks=ntasks,
            pip_ntasks=pip_ntasks,
            nports=nports,
            pip_nports=pip_nports,
            cnt_alloc=cnt_alloc,
            cnt_pip=cnt_pip,
            q_alloc=q_alloc,
            q_pip=q_pip,
            assigned=assigned,
            pipelined=pipelined,
            alloc_cnt=alloc_cnt,
            never_ready=never_ready,
            fit_failed=fit_failed,
            job_start=job_start,
            prev_job=prev_job,
            job_ready=job_ready,
            job_skip=job_skip,
            job_overskip=job_overskip,
        )

    state = jax.lax.fori_loop(0, P + 1, step, state)

    # Clear assignments of discarded jobs (their capacity was already
    # restored in-scan at the job boundary).
    jt = jnp.maximum(tasks.job, 0)
    discarded = state.never_ready[jt] & tasks.real
    assigned = jnp.where(discarded, -1, state.assigned)

    return AllocResult(
        assigned=assigned,
        pipelined=state.pipelined,
        never_ready=state.never_ready,
        fit_failed=state.fit_failed,
        idle=state.idle,
        q_alloc=state.q_alloc + state.q_pip,
    )
