"""Device hot-path hygiene analyzer (VCL2xx).

Operates on a registry of HOT FUNCTIONS — the solve/commit lanes whose
wall-clock is the scheduler's cycle time.  Three checks:

- **VCL201 implicit host sync**: values that dataflow from a device call
  (the jit entry points in ``DEVICE_FNS``, or attributes of their
  results) must not be consumed by host-forcing operations —
  ``float()``/``int()``/``bool()``/``len()``, ``np.asarray``-family
  calls, ``.item()``/``.tolist()``/``.any()``/``.all()``, iteration, or
  a bare ``if``/``while`` test.  The sanctioned sync is
  ``jax.device_get`` (its result is host memory and untainted);
  ``copy_to_host_async`` starts a transfer without blocking and is
  allowed.  Registry entries may also mark PARAMETERS as device-resident
  (``ops/devsnap.py`` planes arrive through arguments, not calls).
- **VCL202 use-after-donation**: a function jitted with
  ``donate_argnums`` invalidates the buffers at those positions; reading
  the same expression after the call is UB unless it was reassigned
  first (the idiom ``buf = donated_fn(buf, ...)`` is fine).
- **VCL203 jit retrace hazard**: every ``static_argnames`` entry must
  name a parameter of the jitted function, and call sites must not pass
  obviously-unhashable values (list/dict/set displays, ``np.*`` array
  results) as static arguments — both retrace (or crash) on every call.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

from . import astcache
from .findings import Finding

# Call leaf names whose results are device-resident (taint sources).
DEVICE_FNS = {
    "solve_wave", "_solve_wave", "sharded_solve_wave",
    "sharded_solve_wave_cycle", "sharded_solve", "device_put",
    "_scatter_rows", "_scatter_cnt0", "_scatter_profile_tables",
    "solve_fn", "solve_async", "_coarse_shortlist", "frag_scores",
    # Mesh-native sharded solve (ISSUE 7): the shard-local ranking /
    # winner-reduction helper and the cycle's mesh dispatch both return
    # device values.
    "_topk_nodes", "_solve_mesh_dispatch",
    # The synchronous dispatch of the allocate lane (ISSUE 25: the body
    # of the device:dispatch span) returns the solve's device result.
    "_solve_sync",
    # Device-incremental lane (ISSUE 9): the static-plane producer,
    # the warm-shortlist kernel, and the DeviceIncremental services
    # that return their cached device results.
    "_static_planes", "_warm_shortlist", "static_planes", "shortlist",
    # Victim-selection kernel (ISSUE 11): eligibility/order/evictable
    # planes come back device-resident; jax.device_get is the one
    # sanctioned fetch before the host-side greedy runs.
    "victim_scores",
    # Hierarchical block->shard->global selection (ISSUE 12): the
    # merge helper returns device id planes.
    "_merge_block_cands",
    # Topology kernels (ISSUE 20): per-block gang-fit and fabric
    # fragmentation planes come back device-resident; jax.device_get
    # is the sanctioned fetch before host-side block selection.
    "gang_block_fit", "fabric_frag",
}

# Call leaf names that force a device->host sync when fed a device value.
SYNC_CALL_FNS = {
    "float", "int", "bool", "len", "asarray", "array",
    "ascontiguousarray", "_np", "bincount", "flatnonzero",
    "count_nonzero",
}

# Method names that force a sync on a device value.
SYNC_METHODS = {"item", "tolist", "any", "all", "min", "max", "sum",
                "astype"}

# The sanctioned fetch: results are host memory (clears taint).
SANCTIONED_FETCH = {"device_get", "block_until_ready"}

# Methods that are safe on a device value (no sync).
SAFE_METHODS = {"copy_to_host_async", "_replace", "addressable_shards"}


@dataclass
class HotEntry:
    """One registry row: a function to analyze.

    ``qualname`` is ``func`` or ``Class.method``; ``device_params`` lists
    dotted parameter paths that arrive device-resident (e.g.
    ``nodes.taint_bits``) — reads through them count as device values.
    """

    qualname: str
    device_params: Tuple[str, ...] = ()


# module path (repo-relative) -> entries.  This is the hot registry the
# tentpole prescribes; extend it when a new lane joins the cycle's
# critical path.
HOT_REGISTRY: Dict[str, List[HotEntry]] = {
    "volcano_tpu/fastpath.py": [
        HotEntry("FastCycle._allocate"),
        HotEntry("FastCycle._dispatch_async"),
        # Mesh dispatch lane (ISSUE 7): wraps sharded_solve_wave_cycle
        # on the cycle thread for both the sync and pipelined paths.
        HotEntry("FastCycle._solve_mesh_dispatch"),
        HotEntry("FastCycle._solve_sync"),
        HotEntry("FastCycle._commit_inflight"),
        HotEntry("FastCycle._commit"),
        HotEntry("FastCycle._solve_inputs"),
        # Two-phase sub-lane/fallback bookkeeping sits between the
        # dispatch and the commit on every cycle.
        HotEntry("FastCycle._record_twophase_lanes"),
        HotEntry("FastCycle._count_shortlist_fb"),
        # Rebalance lane (ISSUE 5): the frag-score kernel dispatch and
        # the pipelined plan commit sit on the cycle thread; an
        # implicit sync here stalls every cycle the lane runs.  (The
        # what-if dispatch/commit bodies moved to volcano_tpu/whatif.py
        # in ISSUE 11 — see that file's entries below.)
        HotEntry("FastCycle._rebalance"),
        HotEntry("FastCycle._plan_rebalance"),
        HotEntry("FastCycle._commit_inflight_plan"),
        # Topology gates (ISSUE 20): the pregate + block-fit dispatch
        # run before every solve round, the post-solve gate on both
        # the sync and pipelined commit paths, the bias builder inside
        # _solve_inputs — all on the cycle thread.
        HotEntry("FastCycle._topo_block_fit"),
        HotEntry("FastCycle._topology_pregate"),
        HotEntry("FastCycle._topo_node_bias"),
        HotEntry("FastCycle._topology_gate"),
    ],
    "volcano_tpu/whatif.py": [
        # The what-if engine (ISSUE 11): hypothetical-solve dispatch,
        # pipelined plan commit, verdict + eviction commit, and the
        # preempt/reclaim planners that dispatch the victim kernel —
        # all on the cycle thread.
        HotEntry("whatif_inputs"),
        HotEntry("dispatch_plan"),
        HotEntry("commit_inflight_plan"),
        HotEntry("apply_plan"),
        HotEntry("commit_plan"),
        HotEntry("_plan_evict"),
        HotEntry("_plan_evict_gang"),
        # The victim table a plan try reads (ISSUE 50): built once per
        # state of the mirror, from host arrays alone.
        HotEntry("VictimTable.__init__"),
        HotEntry("run_evict_action"),
    ],
    "volcano_tpu/ops/victim.py": [
        # The jitted victim-selection kernel (a VCL201 taint source),
        # the host gate asked before it and the host-only greedy
        # selection over its fetched planes.
        HotEntry("victim_scores"),
        HotEntry("queue_min_prio"),
        HotEntry("may_be_eligible"),
        HotEntry("select_victims"),
        HotEntry("fit_counts"),
        HotEntry("queue_shares"),
    ],
    "volcano_tpu/ops/wave.py": [
        # The devsnap planes (allocatable/max_tasks/ready/label_bits/
        # taint_bits) and the two-phase class planes arrive
        # device-resident from FastCycle._solve_inputs.
        HotEntry("solve_wave", device_params=(
            "nodes.allocatable", "nodes.max_tasks", "nodes.ready",
            "nodes.label_bits", "nodes.taint_bits",
            "node_classes.class_id", "node_classes.label_bits",
            "node_classes.taint_bits", "node_classes.ready",
        )),
    ],
    "volcano_tpu/ops/devincr.py": [
        # Device-incremental services (ISSUE 9): they juggle the
        # persistent device planes on the cycle thread — an implicit
        # sync here (fetching a cached plane back just to inspect it)
        # would stall every steady-state dispatch.
        HotEntry("DeviceIncremental.static_planes"),
        HotEntry("DeviceIncremental.shortlist"),
    ],
    "volcano_tpu/ops/devsnap.py": [
        HotEntry("DeviceSnapshot.node_planes"),
        HotEntry("DeviceSnapshot.class_tables"),
        # Mesh-aware placement helpers (ISSUE 7): commit planes/deltas
        # with the node-axis sharding on the cycle thread.
        HotEntry("DeviceSnapshot._put_plane"),
        HotEntry("DeviceSnapshot._put_delta"),
    ],
    "volcano_tpu/fastpath_incr.py": [
        # Incremental host-lane delta scatters (ISSUE 8): host-only
        # numpy by contract — registered so a device value leaking into
        # the derive refresh trips VCL201 instead of a per-cycle sync.
        HotEntry("CycleAggregates.refresh"),
        HotEntry("CycleAggregates._apply_delta"),
        HotEntry("CycleAggregates._scatter_side"),
        HotEntry("CycleAggregates.live_status_counts"),
        HotEntry("_build_aggregates"),
        HotEntry("rank_from_cols"),
        HotEntry("_lex_searchsorted"),
    ],
    "volcano_tpu/ops/nodeclass.py": [
        # Host-only by contract (numpy planes in, numpy planes out);
        # registered so an accidental device value reaching the class
        # builder trips VCL201 instead of a silent per-cycle sync.
        HotEntry("build_node_classes"),
    ],
    "volcano_tpu/ops/rebalance.py": [
        # The jitted frag-score kernel and the host-only greedy drain
        # selection (fetched numpy in by contract, like the class
        # builder above).
        HotEntry("frag_scores"),
        HotEntry("select_drain_set"),
    ],
    "volcano_tpu/ops/topology.py": [
        # The jitted block-fit/frag kernels (VCL201 taint sources) and
        # the host-only selection + bias builders over fetched planes.
        HotEntry("gang_block_fit"),
        HotEntry("fabric_frag"),
        HotEntry("select_block"),
        HotEntry("contig_bias"),
    ],
    "volcano_tpu/parallel/mesh.py": [
        HotEntry("shard_wave_inputs"),
        HotEntry("sharded_solve_wave_cycle"),
    ],
    "volcano_tpu/pipeline.py": [
        HotEntry("InflightSolve.fetch"),
        HotEntry("InflightPlan.fetch"),
    ],
}


# ---- VCL204: chunk-budget routing of full-N device temporaries ------
# A jitted function in these files that materializes a fresh device
# array whose LEADING dimension is a parameter's ``.shape[0]`` (a
# full-N node plane / full-P pod plane temporary) must appear in
# ``CHUNK_BUDGET_REGISTRY`` — registration records that its peak
# footprint is bounded by a reviewed chunk/budget mechanism (the
# lax.map profile streams and keyspace gate in ops/wave.py,
# the devsnap delta-scatter budget, pow2-padded fixed planes in the
# victim/rebalance kernels).  A NEW device fn declaring [N, *] planes
# trips VCL204 until it routes through the chunk-budget machinery and
# is registered here — the scale-tier guard: at 100k nodes x 1M pods
# an unbudgeted full-N temporary is the difference between fitting a
# chip and OOMing it.
BUDGET_FILES = {
    "volcano_tpu/ops/wave.py",
    "volcano_tpu/ops/devsnap.py",
    "volcano_tpu/ops/devincr.py",
    "volcano_tpu/ops/victim.py",
    "volcano_tpu/ops/rebalance.py",
    "volcano_tpu/ops/topology.py",
}
CHUNK_BUDGET_REGISTRY: Dict[str, Set[str]] = {
    "volcano_tpu/ops/wave.py": {
        # Profile axes stream through lax.map in COARSE_CHUNK rows;
        # conflict buffers sit behind the keyspace gate.
        "_solve_wave", "_coarse_shortlist", "_warm_shortlist",
        "_static_planes",
    },
    "volcano_tpu/ops/victim.py": {
        # Planes are pow2-padded to the _solve_inputs buckets — fixed
        # [N]-bounded state, no [N, N]-class temporaries.
        "victim_scores",
    },
    "volcano_tpu/ops/rebalance.py": {
        "frag_scores",
    },
    "volcano_tpu/ops/topology.py": {
        # Node/profile/block axes are pow2-padded to the
        # _topo_block_fit buckets — fixed [N]- and [B, U]-bounded
        # state, no [N, N]-class temporaries.
        "gang_block_fit", "fabric_frag",
    },
}

_ARRAY_CREATE_FNS = {"zeros", "ones", "full", "empty"}


def _shape0_param_root(node: ast.AST, params: Set[str]):
    """The parameter name when ``node`` is ``<param>[.attrs...].shape[0]``
    (optionally wrapped in ``int(...)``), else None."""
    if isinstance(node, ast.Call) and _leaf_name(node.func) == "int" \
            and len(node.args) == 1:
        node = node.args[0]
    if not isinstance(node, ast.Subscript):
        return None
    sl = node.slice
    if isinstance(sl, ast.Index):  # pragma: no cover - py<3.9 form
        sl = sl.value
    if not (isinstance(sl, ast.Constant) and sl.value == 0):
        return None
    base = node.value
    if not (isinstance(base, ast.Attribute) and base.attr == "shape"):
        return None
    root = _dotted(base.value)
    if root is None:
        return None
    head = root.split(".")[0]
    return head if head in params else None


def check_chunk_budget(path: str, tree: ast.Module,
                       jits: Dict[str, JitInfo]) -> List[Finding]:
    """VCL204: unchunked full-N temporaries in unregistered jitted fns
    of the solve-lane files (see BUDGET_FILES)."""
    findings: List[Finding] = []
    if path not in BUDGET_FILES:
        return findings
    allowed = CHUNK_BUDGET_REGISTRY.get(path, set())
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        info = jits.get(fn.name)
        if info is None or fn.name in allowed:
            continue
        params = set(info.params)
        size_vars: Set[str] = set()
        for stmt in ast.walk(fn):
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                if _shape0_param_root(stmt.value, params) is not None:
                    size_vars.add(stmt.targets[0].id)
        if not size_vars:
            continue
        for call in ast.walk(fn):
            if not isinstance(call, ast.Call):
                continue
            if _leaf_name(call.func) not in _ARRAY_CREATE_FNS \
                    or not call.args:
                continue
            shape = call.args[0]
            first = None
            if isinstance(shape, (ast.Tuple, ast.List)) and shape.elts:
                first = shape.elts[0]
            elif isinstance(shape, ast.Name):
                first = shape
            if isinstance(first, ast.Name) and first.id in size_vars:
                findings.append(Finding(
                    "VCL204", path, call.lineno,
                    f"jitted fn {fn.name} materializes a full-"
                    f"{first.id} temporary outside the chunk-budget "
                    "registry (route it through the chunk/budget "
                    "machinery and register it in "
                    "CHUNK_BUDGET_REGISTRY)",
                ))
    return findings


@dataclass
class JitInfo:
    """A function jitted in the analyzed module."""

    name: str
    params: List[str]
    static_argnames: List[str]
    donate_argnums: List[int]
    line: int


def _leaf_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _expr_key(node: ast.AST) -> str:
    """Source-level key for an expression (ctx-insensitive, so a Store
    and a Load of the same subscript compare equal)."""
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - malformed synthetic nodes
        return ast.dump(node)


def _const_tuple(node: ast.AST) -> List[str]:
    """String elements of a tuple/list literal of constants."""
    out: List[str] = []
    if isinstance(node, (ast.Tuple, ast.List)):
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
    elif isinstance(node, ast.Constant) and isinstance(node.value, str):
        out.append(node.value)
    return out


def _const_ints(node: ast.AST) -> List[int]:
    out: List[int] = []
    if isinstance(node, (ast.Tuple, ast.List)):
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, int):
                out.append(el.value)
    elif isinstance(node, ast.Constant) and isinstance(node.value, int):
        out.append(node.value)
    return out


def collect_jits(tree: ast.Module) -> Dict[str, JitInfo]:
    """Find ``@jax.jit`` / ``@partial(jax.jit, ...)`` functions and their
    static/donate declarations."""
    out: Dict[str, JitInfo] = {}
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            static: List[str] = []
            donate: List[int] = []
            is_jit = False
            if isinstance(dec, ast.Call):
                callee = _dotted(dec.func) or ""
                if callee.endswith("partial") and dec.args:
                    inner = _dotted(dec.args[0]) or ""
                    if inner.endswith("jit"):
                        is_jit = True
                elif callee.endswith("jit"):
                    is_jit = True
                if is_jit:
                    for kw in dec.keywords:
                        if kw.arg == "static_argnames":
                            static = _const_tuple(kw.value)
                        elif kw.arg == "donate_argnums":
                            donate = _const_ints(kw.value)
            elif (_dotted(dec) or "").endswith("jit"):
                is_jit = True
            if is_jit:
                # Keyword-only params count: ``*, n_blocks`` statics
                # (ops/topology.gang_block_fit) are legal jit statics.
                params = [a.arg for a in
                          node.args.args + node.args.kwonlyargs]
                out[node.name] = JitInfo(
                    node.name, params, static, donate, node.lineno
                )
                break
    return out


def check_jit_declarations(path: str,
                           jits: Dict[str, JitInfo]) -> List[Finding]:
    """VCL203 structural check: static_argnames must name real params."""
    findings: List[Finding] = []
    for info in jits.values():
        for name in info.static_argnames:
            if name not in info.params:
                findings.append(Finding(
                    "VCL203", path, info.line,
                    f"static_argnames entry '{name}' is not a parameter "
                    f"of {info.name} (drifted signature retraces or "
                    "fails on every call)",
                ))
        for pos in info.donate_argnums:
            if pos >= len(info.params):
                findings.append(Finding(
                    "VCL203", path, info.line,
                    f"donate_argnums position {pos} is out of range for "
                    f"{info.name} ({len(info.params)} parameters)",
                ))
    return findings


class _HotChecker(ast.NodeVisitor):
    """Per-function taint walk (statement order = lexical order; the hot
    lanes are straight-line code with simple loops, which this models
    faithfully enough to be load-bearing)."""

    def __init__(self, path: str, entry: HotEntry,
                 jits: Dict[str, JitInfo], findings: List[Finding]):
        self.path = path
        self.entry = entry
        self.jits = jits
        self.findings = findings
        self.tainted: Set[str] = set(entry.device_params)
        self.donated: Dict[str, int] = {}  # dotted expr -> line donated

    # -------------------------------------------------------------- taint

    def _is_tainted(self, node: ast.AST) -> bool:
        # A call to a device fn used inline is tainted.
        if isinstance(node, ast.Call):
            leaf = _leaf_name(node.func)
            if leaf in DEVICE_FNS:
                return True
            if leaf in SANCTIONED_FETCH:
                return False
            return False
        if isinstance(node, ast.Subscript):
            return self._is_tainted(node.value)
        dotted = _dotted(node)
        if dotted is None:
            return False
        if dotted in self.tainted:
            return True
        # attribute of a tainted value (result.assigned)
        parts = dotted.split(".")
        for i in range(1, len(parts)):
            if ".".join(parts[:i]) in self.tainted:
                return True
        return False

    def _taint_targets(self, targets: Sequence[ast.AST]) -> None:
        for tgt in targets:
            if isinstance(tgt, ast.Tuple):
                self._taint_targets(tgt.elts)
                continue
            dotted = _dotted(tgt)
            if dotted is not None:
                self.tainted.add(dotted)

    def _untaint_targets(self, targets: Sequence[ast.AST]) -> None:
        for tgt in targets:
            if isinstance(tgt, ast.Tuple):
                self._untaint_targets(tgt.elts)
                continue
            dotted = _dotted(tgt)
            if dotted is not None:
                self.tainted.discard(dotted)
                self.donated.pop(dotted, None)

    # ------------------------------------------------------------- visits

    def visit_Assign(self, node: ast.Assign) -> None:
        self.visit(node.value)
        value_tainted = self._is_tainted(node.value)
        # donation bookkeeping: donated exprs reassigned by this very
        # statement (buf = donated_fn(buf, ...)) are fresh again.
        if value_tainted:
            self._taint_targets(node.targets)
        else:
            self._untaint_targets(node.targets)
        for tgt in node.targets:
            dotted = _dotted(tgt) or (
                _dotted(tgt.value) if isinstance(tgt, ast.Subscript)
                else None
            )
            if dotted is not None:
                self.donated.pop(dotted, None)
            self.donated.pop(_expr_key(tgt), None)

    def visit_Call(self, node: ast.Call) -> None:
        leaf = _leaf_name(node.func)
        info = self.jits.get(leaf) if leaf else None
        # -------- VCL201: host-sync calls on tainted args
        if leaf in SYNC_CALL_FNS:
            for arg in node.args:
                if self._is_tainted(arg):
                    self.findings.append(Finding(
                        "VCL201", self.path, node.lineno,
                        f"{leaf}() on a device value forces an implicit "
                        "host sync in a hot function (fetch via "
                        "jax.device_get at the sanctioned sync point)",
                    ))
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in SYNC_METHODS
                and self._is_tainted(node.func.value)):
            self.findings.append(Finding(
                "VCL201", self.path, node.lineno,
                f".{node.func.attr}() on a device value forces an "
                "implicit host sync in a hot function",
            ))
        # -------- VCL203: unhashable static args at call sites
        if info is not None and info.static_argnames:
            for kw in node.keywords:
                if kw.arg in info.static_argnames:
                    bad = None
                    if isinstance(kw.value, (ast.List, ast.Dict, ast.Set)):
                        bad = "an unhashable literal"
                    elif isinstance(kw.value, ast.Call):
                        cleaf = _dotted(kw.value.func) or ""
                        if cleaf.startswith("np.") \
                                or cleaf.startswith("numpy."):
                            bad = "a numpy array expression"
                    if bad is not None:
                        self.findings.append(Finding(
                            "VCL203", self.path, node.lineno,
                            f"static argument '{kw.arg}' of {leaf} is "
                            f"{bad}: unhashable statics fail or retrace "
                            "every call",
                        ))
        self.generic_visit(node)
        # -------- VCL202: donation bookkeeping AFTER visiting children,
        # so the donated argument's own occurrence at the call site is
        # not flagged as a use-after-donation.
        if info is not None and info.donate_argnums:
            for pos in info.donate_argnums:
                if pos < len(node.args):
                    arg = node.args[pos]
                    key = _dotted(arg) or _expr_key(arg)
                    self.donated[key] = node.lineno

    def _check_use(self, node: ast.AST, what: str) -> None:
        key = _dotted(node) or (
            _expr_key(node) if isinstance(node, ast.Subscript) else None
        )
        if key is not None and key in self.donated:
            self.findings.append(Finding(
                "VCL202", self.path, node.lineno,
                f"{what} '{key}' after it was donated at line "
                f"{self.donated[key]} (donate_argnums invalidates the "
                "buffer)",
            ))

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if isinstance(node.ctx, ast.Load):
            self._check_use(node, "read of")
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            self._check_use(node, "read of")

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if isinstance(node.ctx, ast.Load):
            self._check_use(node, "read of")
        self.generic_visit(node)

    def visit_For(self, node: ast.For) -> None:
        if self._is_tainted(node.iter):
            self.findings.append(Finding(
                "VCL201", self.path, node.lineno,
                "iteration over a device value forces a per-element "
                "host sync in a hot function",
            ))
        self.generic_visit(node)

    def visit_If(self, node: ast.If) -> None:
        if self._is_tainted(node.test):
            self.findings.append(Finding(
                "VCL201", self.path, node.lineno,
                "branching on a device value forces an implicit host "
                "sync in a hot function",
            ))
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        if self._is_tainted(node.test):
            self.findings.append(Finding(
                "VCL201", self.path, node.lineno,
                "looping on a device value forces an implicit host sync "
                "in a hot function",
            ))
        self.generic_visit(node)

    def visit_FunctionDef(self, node) -> None:
        return  # closures analyzed separately if registered

    visit_AsyncFunctionDef = visit_FunctionDef


def _find_function(tree: ast.Module, qualname: str):
    parts = qualname.split(".")
    scope = tree.body
    target = None
    for i, part in enumerate(parts):
        target = None
        for node in scope:
            if isinstance(node, ast.ClassDef) and node.name == part:
                scope = node.body
                target = node
                break
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name == part:
                target = node
                break
        if target is None:
            return None
        if isinstance(target, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and i == len(parts) - 1:
            return target
    return target if isinstance(
        target, (ast.FunctionDef, ast.AsyncFunctionDef)) else None


def analyze_file(path: str, source: str,
                 entries: Sequence[HotEntry]) -> List[Finding]:
    """Run the hot-path checks for the registered functions of one file.
    Returns RAW findings (suppressions applied by the caller)."""
    findings: List[Finding] = []
    try:
        tree = astcache.parse(source)
    except SyntaxError as err:
        return [Finding("VCL001", path, err.lineno or 1,
                        f"file does not parse: {err.msg}")]
    jits = collect_jits(tree)
    findings.extend(check_jit_declarations(path, jits))
    findings.extend(check_chunk_budget(path, tree, jits))
    for entry in entries:
        fn = _find_function(tree, entry.qualname)
        if fn is None:
            findings.append(Finding(
                "VCL001", path, 1,
                f"hot-registry entry {entry.qualname} not found "
                "(registry drifted from the code)",
            ))
            continue
        checker = _HotChecker(path, entry, jits, findings)
        for stmt in fn.body:
            checker.visit(stmt)
    return findings
