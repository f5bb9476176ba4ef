#!/usr/bin/env python
"""Minimal repro for the 50k-node x 500k-pod + inter-pod-affinity device
failure of earlier rounds (never re-run on the current machine).

Runs BASELINE config 5 FULL with affinity, logging every chunked solve
(jobs, rows, active terms, padded count-tensor bytes) to an artifact
JSONL so the crash point is recorded even when the TPU worker dies
mid-solve.  Knobs:

  VOLCANO_TPU_AFF_BUDGET_MB   chunk memory budget (default 1024)
  REPRO_RELEASE=1             aggressively release device state between
                              chunks (delete result refs + clear jax
                              caches every chunk batch) — the "device
                              re-attach" experiment
  REPRO_CYCLES=N              run N full cycles on FRESH stores in one
                              process (default 1).  Round-3 finding: one
                              cycle completes; the historic worker crash
                              reproduces on the SECOND full-scale cycle
                              of the same process (cumulative device
                              state).
  REPRO_NODES / REPRO_PODS    override the 50000 x 500000 shape

Artifact: hack/hyperscale_affinity_repro.jsonl (one line per chunk +
a final status line).  Exit code 0 = completed, nonzero = crashed; the
artifact's last line shows how far it got.

Usage:  python hack/repro_hyperscale_affinity.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

ARTIFACT = os.path.join(os.path.dirname(__file__),
                        "hyperscale_affinity_repro.jsonl")


def main() -> int:
    n_nodes = int(os.environ.get("REPRO_NODES", 50000))
    n_pods = int(os.environ.get("REPRO_PODS", 500000))
    release = os.environ.get("REPRO_RELEASE") == "1"

    art = open(ARTIFACT, "w")

    def emit(rec):
        rec["t"] = round(time.time(), 3)
        art.write(json.dumps(rec) + "\n")
        art.flush()
        os.fsync(art.fileno())
        print(rec, flush=True)

    emit({"event": "start", "nodes": n_nodes, "pods": n_pods,
          "budget_mb": os.environ.get("VOLCANO_TPU_AFF_BUDGET_MB",
                                      "1024"),
          "release": release})

    from volcano_tpu import fastpath
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.synth import synthetic_cluster

    # Instrument the chunker: record every chunk the solver sees.
    orig_chunks = fastpath.FastCycle._solve_chunks
    chunk_no = {"i": 0}

    def chunks_logged(self, solve_jobs, task_rows):
        for cjobs, crows in orig_chunks(self, solve_jobs, task_rows):
            m = self.m
            import numpy as np

            er_a, ei_a = m.c_ip_aff.gather(crows)
            er_n, ei_n = m.c_ip_anti.gather(crows)
            er_s, ei_s, _ = m.c_ip_soft.gather(crows)
            terms = np.concatenate([ei_a, ei_n, ei_s])
            E = len(np.unique(terms)) if len(terms) else 0
            D = max(1, len(m.domains))
            from volcano_tpu.ops.wave import bucket_pow2

            cost = float(bucket_pow2(E, floor=1)) * D * 8.0 if E else 0.0
            chunk_no["i"] += 1
            emit({"event": "chunk", "n": chunk_no["i"],
                  "jobs": len(cjobs), "rows": int(len(crows)),
                  "active_terms": int(E), "domains": int(D),
                  "count_tensor_mb": round(cost / 1e6, 1)})
            yield cjobs, crows
            emit({"event": "chunk_done", "n": chunk_no["i"]})
            if release:
                import gc

                import jax

                gc.collect()
                jax.clear_caches()
                emit({"event": "released", "n": chunk_no["i"]})

    fastpath.FastCycle._solve_chunks = chunks_logged

    n_cycles = int(os.environ.get("REPRO_CYCLES", 1))
    for cyc in range(n_cycles):
        emit({"event": "build_store", "cycle": cyc})
        store = synthetic_cluster(
            n_nodes=n_nodes, n_pods=n_pods, gang_size=8, zones=16,
            affinity_fraction=0.05, anti_affinity_fraction=0.05,
            spread_fraction=0.1, seed=cyc,
        )
        store.async_bind = True
        emit({"event": "cycle_start", "cycle": cyc})
        t0 = time.perf_counter()
        try:
            Scheduler(store).run_once()
        except BaseException as e:  # noqa: BLE001 — record then re-raise
            emit({"event": "crash", "cycle": cyc,
                  "error": repr(e)[:500],
                  "after_s": round(time.perf_counter() - t0, 1),
                  "chunks_done": chunk_no["i"]})
            raise
        store.flush_binds()
        bound = sum(1 for p in store.pods.values() if p.node_name)
        emit({"event": "done", "cycle": cyc,
              "cycle_s": round(time.perf_counter() - t0, 1),
              "bound": bound, "chunks": chunk_no["i"]})
        store.close()
        del store
        if release:
            import gc

            import jax

            gc.collect()
            jax.clear_caches()
            emit({"event": "released", "cycle": cyc})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
