"""From a ``jax.profiler`` trace to device busy time, idle share, per-program
totals and labelled idle gaps.

The reduction works on a plain structure, so that it can be checked on a
small trace kept beside this file (``trace_sample.json``):

    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [[name, start_ns, duration_ns], ...]}]}]}

``load_xplane`` makes that structure from an ``.xplane.pb`` file with
nothing but JAX.  What a v5e trace looks like (looked at by hand, see
PERF.md): one plane per chip named ``/device:TPU:<n>``, with a line
``XLA Ops`` (one event per operation that ran on the chip), a line
``XLA Modules`` (one event per jitted program, named ``jit_<fn>(<id>)``)
and a line ``Steps``; host threads are lines of the plane ``/host:CPU``,
where the benchmark's ``bench:submit`` / ``bench:schedule`` /
``bench:complete`` annotations land.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint ones, sorted."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(busy: List[Tuple[int, int]], s: int, e: int) -> int:
    return sum(max(0, min(e, be) - max(s, bs)) for bs, be in busy)


def _line(plane: dict, name: str) -> Optional[dict]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def bench_spans(trace: dict) -> List[Tuple[str, int, int]]:
    """The benchmark's own annotations, ``(label, start, end)``, wherever
    the profiler put them (a host thread's line)."""
    out = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((name[len(SPAN_PREFIX):], start, start + dur))
    return sorted(out, key=lambda x: x[1])


def reduce(trace: dict, window_s: Optional[float] = None, top: int = 10) -> dict:
    """``busy_s``: seconds in which any operation ran on a chip, averaged
    over the chips in the trace.  ``window_s``: as given (the host's clock
    around the traced rounds) or, failing that, the extent of the
    benchmark's spans in the trace.  ``device_ops``: programs (``XLA
    Modules``; operations where the trace has no such line) by total time.
    ``idle_gaps``: the longest stretches with nothing on the first chip, cut
    at the benchmark's span boundaries and labelled by the span.  Returns
    ``{}`` when the trace has no device plane (a CPU run)."""
    planes = device_planes(trace)
    if not planes:
        return {}
    busy_ns = []
    per_name: Dict[str, int] = {}
    first_busy: List[Tuple[int, int]] = []
    for i, plane in enumerate(planes):
        ops = _line(plane, OPS_LINE)
        events = ops["events"] if ops else [
            ev for line in plane["lines"] if line["name"] != "Steps"
            for ev in line["events"]]
        busy = union([(s, s + d) for _n, s, d in events if d > 0])
        busy_ns.append(sum(e - s for s, e in busy))
        if i == 0:
            first_busy = busy
        named = _line(plane, MODULES_LINE) or ops
        for name, _s, dur in (named["events"] if named else []):
            per_name[name] = per_name.get(name, 0) + dur
    spans = bench_spans(trace)
    if window_s is None and spans:
        window_s = (max(e for _l, _s, e in spans)
                    - min(s for _l, s, _e in spans)) / 1e9
    gaps = []
    for label, s, e in spans:
        inside = union([(max(s, bs), min(e, be)) for bs, be in first_busy
                        if min(e, be) > max(s, bs)])
        cursor = s
        for bs, be in inside + [(e, e)]:
            if bs > cursor:
                gaps.append((label, (bs - cursor) / 1e9))
            cursor = max(cursor, be)
    gaps.sort(key=lambda g: -g[1])
    n = len(planes)
    return {
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": window_s,
        "chips": n,
        "device_ops": [[k, v / 1e9 / n] for k, v in sorted(
            per_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label, s] for label, s in gaps[:top]],
        "program_s": {k: v / 1e9 / n for k, v in per_name.items()},
    }


def describe(trace: dict, top: int = 8) -> List[str]:
    """Planes, lines and the commonest event names: what one reads before
    writing code against a trace."""
    out = []
    for plane in trace["planes"]:
        out.append(f"plane {plane['name']!r}: {len(plane['lines'])} lines")
        for line in plane["lines"]:
            tot: Dict[str, int] = {}
            for name, _s, dur in line["events"]:
                tot[name] = tot.get(name, 0) + dur
            names = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
            out.append(f"  line {line['name']!r}: {len(line['events'])} events; "
                       + "; ".join(f"{k[:60]}={v / 1e6:.3f}ms" for k, v in names))
    return out
