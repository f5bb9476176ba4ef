"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, in a traced run,
``breakdown``); the lines before it say what was counted and compared.
``--trace 0`` reports the cell's end-to-end metrics with the profiler off,
``--trace 1`` its per-layer metrics.  See README.md beside this file.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

OUT_DIR = ROOT / ".bench_out"
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def say(*parts) -> None:
    print(*parts, flush=True)


class Compiles:
    """Names of the programs JAX lowered (one event per new jit
    specialisation, compiled or loaded from the persistent cache), via
    ``jax.monitoring``.  From ``chip_smoke.py``'s ``_Compiles``."""

    def __init__(self):
        import jax.monitoring

        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == LOWERING_EVENT:
            self.names.append(str(kw.get("fun_name", "?")))


class Profiler:
    """A ``jax.profiler`` trace over whole rounds from the start of the
    window until ``seconds`` have passed (at least one round): traces are
    large, and tracing slows the host.  Python frames are not traced; the
    benchmark's ``bench:*`` annotations are."""

    def __init__(self, trace_dir: Path, seconds: float):
        import jax

        self.trace_dir = trace_dir
        self.seconds = seconds
        self.rounds = 0
        self.window_s = None
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        self.on = True
        self.t0 = time.perf_counter()

    def after_round(self, _round) -> None:
        if self.on:
            self.rounds += 1
            if time.perf_counter() - self.t0 >= self.seconds:
                self.stop()

    def stop(self) -> None:
        if self.on:
            import jax

            self.window_s = time.perf_counter() - self.t0
            jax.profiler.stop_trace()
            self.on = False

    def reduce(self) -> dict:
        from benchmark.harness import trace_reduce

        path = trace_reduce.find_xplane(str(self.trace_dir))
        if not path:
            return {}
        return trace_reduce.reduce(trace_reduce.load_xplane(path),
                                   window_s=self.window_s)


def end_to_end(counted, setup_s: float, window_end_ns: int) -> dict:
    """The end-to-end metrics, from the benchmark's own stamps alone."""
    import numpy as np

    bound = 0
    backlog_ms = []
    latencies = []
    for r in counted:
        if r.plan.may_wait:
            continue                    # over the pods that may not wait
        index = {k: i for i, k in enumerate(r.plan.keys())}
        t_bind = np.full(r.plan.n_pods, -1, dtype=np.int64)
        for t, keys, _hosts in r.arrivals:
            for k in keys:
                i = index.get(k)
                if i is not None and t_bind[i] < 0:
                    t_bind[i] = t
        seen = t_bind >= 0
        bound += int(seen.sum())
        if seen.all():
            # The backlog stands once the user has made the last call: the
            # last add_pod; under entry: jobs the last add_batch_job, and
            # not the controllers' first pump after it.
            stands = r.t_submitted if r.jobs is None else r.t_admitted
            backlog_ms.append((int(t_bind.max()) - stands) / 1e6)
        # A pod not bound within its round misses any limit: its wait is
        # taken to the end of the window.
        t_bind[~seen] = window_end_ns
        latencies.append((t_bind - r.submit_ns) / 1e6)
    # All the work over all the time of the window: from the first submit
    # to the end of the last counted round, the client's building of the
    # next batch's objects between rounds included (the closed loop's one
    # client is part of the served path).  The rounds' own time (submit +
    # schedule + complete, without it) is printed beside the result.
    round_s = sum(r.t_end - r.t_start for r in counted) / 1e9
    span_s = (counted[-1].t_end - counted[0].t_start) / 1e9
    lat = np.concatenate(latencies)
    return {
        "bind_rate": bound / span_s,
        "backlog_to_bind_ms": (statistics.median(backlog_ms)
                               if backlog_ms else float(lat.max())),
        "submit_to_bind_p95_ms": float(np.percentile(lat, 95)),
        "setup_s": setup_s,
        "_bound": bound, "_span_s": span_s, "_round_s": round_s,
        "_pods": int(len(lat)),
        "_p50_ms": float(np.percentile(lat, 50)),
    }


def jobs_line(counted, all_rounds, max_pumps: int) -> str:
    """Under ``entry: jobs``: the pumps of a round by phase and their own
    seconds, and every phase that used up its pumps, with the longest pump
    of that phase (a run on a program that cannot keep up ends, loudly)."""
    phases = list(counted[0].pumps)
    line = ("window: gangs enter as Jobs; pumps a round by phase (median) "
            + ", ".join(f"{p} {statistics.median(r.pumps[p] for r in counted)}"
                        for p in phases)
            + "; the pumps' own seconds (median) "
            + ", ".join(f"{p} {statistics.median(r.pump_s[p] for r in counted):.3f}"
                        for p in phases))
    out = {}
    for r in all_rounds:
        for p in r.pumps_used_up:
            n, worst = out.get(p, (0, 0.0))
            out[p] = (n + 1, max(worst, r.pump_s[p]))
    if not out:
        return line + f"; no phase of the run used up its {max_pumps} pumps"
    return line + f"; OUT OF PUMPS (max_pumps {max_pumps}): " + ", ".join(
        f"{p} in {n} rounds, its pumps took up to {worst:.3f} s a round"
        for p, (n, worst) in out.items())


def device_dict(info: dict, profile: dict, on_cpu: bool) -> dict:
    import jax

    peak = None
    if not on_cpu:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        peak = max(peaks) if peaks else None
    out = {"platform": info["platform"], "kind": info["kind"],
           "count": info["count"], "memory_peak_bytes": peak}
    if profile:
        out["busy_s"] = profile.get("busy_s")
        out["window_s"] = profile.get("window_s")
    return out


def set_up(cell, seed: int, trace: bool, make_scheduler=None,
           bind_wait_s: float = None):
    """Everything before the window: the nodes, the resident set and the
    warm-up rounds on one ``Driver``, which is then the object the window
    times and the probe goes on with.  Returns ``(driver, gen, sizes)``."""
    import jax

    from benchmark.harness import generate, loop

    sizes = cell.sizes()
    if bind_wait_s is not None:
        sizes["bind_wait_s"] = bind_wait_s
    gen = generate.Generator(cell.config, seed, entry=sizes["entry"])
    driver = loop.Driver(cell.config, max_cycles=sizes["max_cycles"],
                         make_scheduler=make_scheduler or loop.default_scheduler,
                         read_lanes=trace, bind_wait_s=sizes["bind_wait_s"],
                         annotate=jax.profiler.TraceAnnotation if trace else None,
                         termination_cycles=sizes["termination_cycles"],
                         settle_cycles=sizes["settle_cycles"],
                         pods_run=sizes["pods_run"], entry=sizes["entry"],
                         max_pumps=sizes["max_pumps"])
    if sizes["resident_pods"]:
        driver.round(gen.plan(sizes["resident_pods"], "resident",
                              klass=sizes["resident_class"]), 0)
    if sizes["waiting_pods"]:
        # The tier that may wait: submitted onto the full cluster, one cycle.
        driver.round(gen.plan(sizes["waiting_pods"], "waiting",
                              klass=sizes["resident_class"], may_wait=True), 0)
    # From here on every batch is of the traffic's batch class, if it names one.
    gen.batch_class = sizes["batch_class"]
    for i in range(sizes["warmup_rounds"]):
        driver.round(gen.plan(sizes["batch_pods"], f"warm{i:02d}"),
                     sizes["batch_pods"])
    return driver, gen, sizes


def require_chips(cell) -> dict:
    """The device as JAX reports it; no accelerator (unless the CPU was
    asked for by name) or too few chips ends the run with no result."""
    from volcano_tpu import device

    device.require_accelerator("benchmark/run.py")
    info = device.device_info()
    if info["platform"] != "cpu" and info["count"] < cell.chips:
        raise SystemExit(f"cell {cell.name} needs {cell.chips} chip(s), JAX "
                         f"reports {info['count']}")
    return info


def run(cell, seed: int, seconds: float, trace: bool, make_scheduler=None,
        bind_wait_s: float = None, t_process: float = None,
        control: bool = False) -> dict:
    """Set-up, window, and after it the probe and the validation of one run;
    returns the result object.  ``make_scheduler`` and ``bind_wait_s`` are
    for the tests, which break the timed path underneath to see ``correct``
    come out false."""
    from benchmark.harness import checks, generate, loop, probe, readers

    t_process = _T_PROCESS if t_process is None else t_process
    info = require_chips(cell)
    on_cpu = info["platform"] == "cpu"
    say(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {seed}, window {seconds} s, trace "
        f"{int(trace)}, device {info}"
        + (" -- a CPU rehearsal: no number below is a device number"
           if on_cpu else ""))

    t_device = time.perf_counter()
    compiles = Compiles()
    driver, gen, sizes = set_up(cell, seed, trace, make_scheduler, bind_wait_s)
    batch = sizes["batch_pods"]
    compiled_before = len(compiles.names)
    setup_s = time.perf_counter() - t_process
    say(f"set-up {setup_s:.3f} s: imports and device {t_device - t_process:.3f}"
        f", nodes and {len(driver.rounds)} rounds "
        f"{time.perf_counter() - t_device:.3f} ({compiled_before} programs "
        "lowered, compiled or loaded from the cache)")

    # ---- the window ------------------------------------------------------
    profiler = Profiler(OUT_DIR / "trace", sizes["profile_seconds"]) if trace else None
    try:
        counted = loop.run_window(driver, gen, batch, seconds,
                                  profiler.after_round if profiler else None)
    finally:
        if profiler:
            profiler.stop()
    window_end_ns = time.perf_counter_ns()
    compiled_in_window = compiles.names[compiled_before:]

    # ---- after the window, outside every timed span -----------------------
    profile = profiler.reduce() if profiler else {}
    dev = device_dict(info, profile, on_cpu)
    # The probe goes on with the timed driver: same store, same Scheduler,
    # every node, the cluster as the timed solve leaves it.
    t_probe = time.perf_counter()
    first_probe = probe.drive(driver, gen, batch, cell.config["probe"])
    all_rounds = list(driver.rounds)
    live_keys = driver.live_keys()
    driver.close()
    t_check = time.perf_counter()
    events = [r.events() for r in all_rounds]
    verdict, probe_verdict = probe.check(
        generate.node_names(cell.config), generate.node_alloc(cell.config),
        events, first_probe, live_keys=live_keys)
    t_own = time.perf_counter()
    # The configuration's own guarantees, by name (harness/checks.py).
    verdict.extra = checks.run(cell.home, cell.config, events, {
        "names": generate.node_names(cell.config),
        "labels": generate.node_labels(cell.config)})
    own_s = time.perf_counter() - t_own
    for line in verdict.lines() + probe_verdict.lines():
        say(line)
    if control:
        # Not part of a run: the reading the probe's limit was set from.
        import ml_dtypes

        _, ctl = probe.check(
            generate.node_names(cell.config), generate.node_alloc(cell.config),
            events, first_probe, control_dtype=ml_dtypes.bfloat16)
        say(f"control: with the reference in bfloat16 in the program's place "
            f"{ctl.misses} of {ctl.probes} choices differ, worst shortfall "
            f"{ctl.worst_shortfall:.6f} ("
            + "".join("x" if m else "." for m in ctl.missed) + ")")
    fill = "the probe's fill"
    if cell.config["probe"].get("fill_pods"):
        own = all_rounds[first_probe - 1]
        fill += (f" (and {own.plan.n_pods} pods of its own, placed by the "
                 f"client as pods: {own.spans()['round']:.3f} s; at the first "
                 "probe "
                 + ", ".join(f"{n} nodes hold {k}" for k, n
                             in probe_verdict.nodes_by_pods.items()) + ")")
    say(f"after the window: {fill} and {probe_verdict.probes} "
        f"one-pod cycles {t_check - t_probe:.3f} s, validation and reference "
        f"{time.perf_counter() - t_check:.3f} s, of which the configuration's "
        f"own checks {checks.names(cell.config)} {own_s:.3f} s (none of it in "
        "setup_s or in the window)")

    e2e = end_to_end(counted, setup_s, window_end_ns)
    attempted = e2e["_pods"]
    failed = min(attempted, (attempted - e2e["_bound"]) + verdict.failed
                 + probe_verdict.misses)
    correct = verdict.ok and probe_verdict.ok and e2e["_bound"] == attempted
    say(f"window: {len(counted)} counted rounds of {batch} pods, "
        f"{e2e['_bound']} of {attempted} pods bound within their round; "
        f"cycles per round "
        f"{sorted(set(r.cycles for r in counted))}; submit->bind median "
        f"{e2e['_p50_ms']:.3f} ms")
    own = sorted(r.spans()["round"] for r in counted)
    say(f"window: a round's own time min {own[0]:.3f}, median "
        f"{statistics.median(own):.3f}, max {own[-1]:.3f} s; by phase (median "
        "s) " + ", ".join(
            f"{k} {statistics.median(r.spans()[k] for r in counted):.3f}"
            for k in ("submit", "admit", "pump", "schedule", "reconcile",
                      "complete") if k in counted[0].spans()))
    if sizes["entry"] == "jobs":
        say(jobs_line(counted, all_rounds, sizes["max_pumps"]))
    say(f"window: {sum(len(r.evictions) for r in counted)} evictions seen, "
        f"{sum(len(r.terminations) for r in counted)} terminations ended; "
        f"cycles after the completions per round "
        f"{sorted(set(r.settle_cycles for r in counted))}, settle median "
        f"{statistics.median(r.spans()['settle'] for r in counted):.6f} s; "
        f"longest wait for a cycle's hand-over "
        f"{max(r.wait_s for r in all_rounds):.3f} s, "
        f"{sum(r.waits_timed_out for r in all_rounds)} waits of the run "
        f"reached bind_wait_s ({sizes['bind_wait_s']} s; should be 0)")
    think_s = e2e["_span_s"] - e2e["_round_s"]
    say(f"window: first submit to last completion {e2e['_span_s']:.3f} s "
        f"(what bind_rate runs on), of which {think_s:.3f} s "
        f"({100 * think_s / e2e['_span_s']:.2f} %) the client spent between "
        "rounds building the next batch's objects; on the rounds' own time "
        f"the rate would be {e2e['_bound'] / e2e['_round_s']:.3f} pods/s")
    say(f"window: {len(compiled_in_window)} programs lowered inside the "
        f"window (should be 0): {compiled_in_window[:8]}")
    say(f"peak device memory: {dev['memory_peak_bytes']} bytes"
        if not on_cpu else "peak device memory: not measured (CPU)")

    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        obs = readers.Observed(
            rounds=counted,
            counters={"compiles_in_window": float(len(compiled_in_window))},
            profile=profile, profiled_rounds=profiler.rounds)
        for spec in cell.per_layer:
            value = readers.read(spec, obs)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        off = sum(r.lanes.get("_off_fast_path", 0.0) for r in all_rounds)
        say(f"traced: {int(off)} cycles left the fast path or failed (should "
            "be 0)")
        if profile:
            say(f"traced: profiler covered the first {profiler.rounds} rounds, "
                f"{profile['window_s']:.3f} s; device busy "
                f"{profile['busy_s']:.6f} s, idle share "
                f"{1 - profile['busy_s'] / profile['window_s']:.6f} of those "
                "rounds only")
        else:
            say("traced: no device plane in the trace: device busy time not "
                "measured")
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": int(failed), "metrics": metrics, "device": dev}
    if trace and profile:
        result["breakdown"] = {"device_ops": profile["device_ops"],
                               "idle_gaps": profile["idle_gaps"]}
    # Every number compared, beside its limit: last in the line, and the
    # last lines on standard error.
    compared = {name: {"value": value, "limit": 0}
                for name, value in verdict.compared().items()}
    compared["probe_misses"] = {"value": probe_verdict.misses, "limit": 0}
    compared["not_bound_in_round"] = {"value": attempted - e2e["_bound"],
                                      "limit": 0}
    compared["fullest_node"] = {"value": verdict.worst_fill, "limit": 1.0}
    result["compared"] = compared
    for name, c in compared.items():
        print(f"compared: {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark-file", default=str(ROOT / "BENCHMARK.json"),
                    help="another BENCHMARK.json (the tests' toy cells)")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: also print the control's reading on this run's "
                         "probe record (the benchmark's own runs do not)")
    args = ap.parse_args(argv)
    from benchmark.harness.cell import load_cell, use_checkout_compile_cache

    use_checkout_compile_cache()
    cell = load_cell(args.workload, Path(args.benchmark_file))
    result = run(cell, args.seed, args.seconds, bool(args.trace),
                 control=bool(args.control))
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
