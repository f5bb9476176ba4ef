"""Per-layer metric readers.  A metric is a file ``layer_metrics/<name>.json``
that names one of these readers and its arguments; a reader that finds
nothing to read returns None and the harness leaves the metric out.

    span     a span of the benchmark's loop (submit, schedule, complete,
             settle, round, run_once; under ``entry: jobs`` also admit,
             pump, reconcile), per round; ``per: "pod"`` divides by the
             round's pods; ``minus_all_lanes_except: [...]`` subtracts every
             flight-recorder lane but the listed (nested) ones
    lane     the sum of the named flight-recorder lanes over a round's cycles
    profile  device time from the profiler trace over the profiled rounds:
             ``quantity: "busy"`` (union of all operations) or ``pattern``, a
             regular expression over program names
    counter  a named counter of the run
    record   a number out of a block of the program's cycle records
             (``solve``, ``whatif``, ``between``): ``key`` is dotted, its
             first part the block (``solve.aff_terms``, ``whatif.victims``,
             ``between.gc.gen2.n``); ``reduce``: ``sum`` (default) or ``max``
             over the round's cycles that have the number
    span_self  the self time (its own minus its direct children's) of the
             program's spans of one ``name``, summed over a round's cycles

Every per-round reading is reduced by the median over the counted rounds and
multiplied by ``scale``.
"""

from __future__ import annotations

import re
import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class Observed:
    rounds: List[object]                       # loop.Round, counted ones
    counters: Dict[str, float] = field(default_factory=dict)
    profile: Dict[str, object] = field(default_factory=dict)
    profiled_rounds: int = 0


def _median(values: List[float], scale: float) -> Optional[float]:
    return statistics.median(values) * scale if values else None


def read_span(args: dict, obs: Observed) -> Optional[float]:
    keep = args.get("minus_all_lanes_except")
    values = []
    for r in obs.rounds:
        v = r.spans().get(args["span"])
        if v is None:           # a span of another entry's rounds
            return None
        if keep is not None:
            if not r.lanes:
                return None
            v -= sum(s for name, s in r.lanes.items()
                     if name not in keep and not name.startswith("_"))
        if args.get("per") == "pod":
            v /= max(r.plan.n_pods, 1)
        values.append(v)
    return _median(values, float(args.get("scale", 1.0)))


def read_lane(args: dict, obs: Observed) -> Optional[float]:
    values = [sum(r.lanes.get(name, 0.0) for name in args["lanes"])
              for r in obs.rounds
              if any(name in r.lanes for name in args["lanes"])]
    return _median(values, float(args.get("scale", 1.0)))


def read_profile(args: dict, obs: Observed) -> Optional[float]:
    prof = obs.profile
    if not prof or not obs.profiled_rounds:
        return None
    if args.get("quantity") == "busy":
        total = prof.get("busy_s")
    else:
        pat = re.compile(args["pattern"])
        hits = [s for name, s in prof.get("program_s", {}).items()
                if pat.search(name)]
        total = sum(hits) if hits else None
    if total is None:
        return None
    if args.get("per") == "round":
        total /= obs.profiled_rounds
    return total * float(args.get("scale", 1.0))


def read_counter(args: dict, obs: Observed) -> Optional[float]:
    value = obs.counters.get(args["counter"])
    return None if value is None else value * float(args.get("scale", 1.0))


def _dig(record: dict, key: str):
    value = record
    for part in key.split("."):
        if not isinstance(value, dict) or part not in value:
            return None
        value = value[part]
    return value if isinstance(value, (int, float)) \
        and not isinstance(value, bool) else None


def read_record(args: dict, obs: Observed) -> Optional[float]:
    how = {"sum": sum, "max": max}[args.get("reduce", "sum")]
    values = []
    for r in obs.rounds:
        found = [v for v in (_dig(rec, args["key"]) for rec in r.records)
                 if v is not None]
        if found:
            values.append(float(how(found)))
    return _median(values, float(args.get("scale", 1.0)))


def read_span_self(args: dict, obs: Observed) -> Optional[float]:
    values = []
    for r in obs.rounds:
        total, hit = 0.0, False
        for rec in r.records:
            children: Dict[object, int] = {}
            for _name, dur, _sid, parent in rec["spans"]:
                if parent is not None:
                    children[parent] = children.get(parent, 0) + dur
            for name, dur, sid, _parent in rec["spans"]:
                if name == args["name"]:
                    total += (dur - children.get(sid, 0)) / 1e9
                    hit = True
        if hit:
            values.append(total)
    return _median(values, float(args.get("scale", 1.0)))


READERS = {"span": read_span, "lane": read_lane, "profile": read_profile,
           "counter": read_counter, "record": read_record,
           "span_self": read_span_self}


def read(spec: dict, obs: Observed) -> Optional[float]:
    return READERS[spec["reader"]](spec.get("args", {}), obs)
