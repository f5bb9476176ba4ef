"""A cell, found by its name in ``BENCHMARK.json``: its configuration file,
its traffic file and the per-layer metric files, all data.

A later PR adds a configuration, a traffic mix, a cell or a per-layer metric
by adding files and entries: ``configs/<config>.json``,
``traffic/<mix>.json``, ``layer_metrics/<metric>.json`` and, for a guarantee
of the configuration's own, ``reference/<name>_ref.py`` under the benchmark's
first path, found from the entries of ``BENCHMARK.json`` and the
configuration's ``guarantees.checks``.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from pathlib import Path
from typing import List

from . import checks

ROOT = Path(__file__).resolve().parents[2]


def use_checkout_compile_cache() -> None:
    """The one variable the harness sets, and only when it is unset: JAX's
    persistent compile cache goes to the fixed ``.xla_cache/`` inside the
    checkout that ``scheduler.py`` already uses.  Call before JAX is
    imported."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".xla_cache"))


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]      # BENCHMARK.json entries this cell reports
    per_layer: List[dict]       # entry merged with its layer_metrics file
    home: Path = ROOT / "benchmark"     # where reference/<name>_ref.py lies

    def sizes(self) -> dict:
        """Resident set, batch, waiting pods and warm-up rounds in pods, from
        the traffic's shares of the configuration's backlog, and the
        traffic's other keys with their defaults."""
        backlog = int(self.config["backlog_pods"])
        t = self.traffic
        return {
            "resident_pods": int(round(backlog * float(t.get("resident_fraction", 0.0)))),
            "batch_pods": max(1, int(round(backlog * float(t["batch_fraction"])))),
            "warmup_rounds": int(t.get("warmup_rounds", 1)),
            "max_cycles": int(t.get("max_cycles", 4)),
            "bind_wait_s": float(t.get("bind_wait_s", 10.0)),
            "profile_seconds": float(t.get("profile_seconds", 10.0)),
            # The kubelet's side and the tiers; absent, nothing of them is.
            "termination_cycles": int(t.get("termination_cycles", 0)),
            "settle_cycles": int(t.get("settle_cycles", 0)),
            "pods_run": bool(t.get("pods_run", False)),
            "waiting_pods": int(round(backlog * float(t.get("waiting_fraction", 0.0)))),
            "resident_class": t.get("resident_class"),
            "batch_class": t.get("batch_class"),
            # Where the user enters: "pods" (the store's event API) or
            # "jobs" (admission and the controllers; max_pumps bounds each
            # wait on them).
            "entry": t.get("entry", "pods"),
            "max_pumps": int(t.get("max_pumps", 4)),
        }


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def load_cell(workload: str, benchmark_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    benchmark_file = Path(benchmark_file)
    base = benchmark_file.parent
    bench = _read(benchmark_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no cell {workload!r} in {benchmark_file}; it has "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    home = base / bench["paths"][0]
    config = _read(base / configs[w["config"]]["file"])
    traffic = _read(home / "traffic" / f"{w['traffic']}.json")
    per_layer = []
    for entry in bench["per_layer"]:
        if _applies(entry, workload):
            spec = _read(home / "layer_metrics" / f"{entry['name']}.json")
            per_layer.append({**spec, **entry})
    entry = traffic.get("entry", "pods")
    if entry not in ("pods", "jobs"):
        raise SystemExit(f"traffic/{w['traffic']}.json: entry is {entry!r}; "
                         "the harness has 'pods' and 'jobs'")
    if entry == "jobs":
        if any(float(x) > 0 for x in config.get("affinity_mix", {}).values()):
            raise SystemExit(f"cell {workload}: entry: jobs with a "
                             "configuration that has an affinity_mix: a "
                             "Job's TaskSpec carries no inter-pod terms")
        if not traffic.get("pods_run"):
            raise SystemExit(f"cell {workload}: entry: jobs needs pods_run: "
                             "a Job reads Running only of pods that are "
                             "reported Running")
    for name in checks.names(config):
        if not checks.path_of(home, name).is_file():
            raise SystemExit(f"{configs[w['config']]['file']}: guarantees."
                             f"checks names {name!r}, and there is no "
                             f"{checks.path_of(home, name)}")
    cell = Cell(
        home=home,
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[e for e in bench["end_to_end"] if _applies(e, workload)],
        per_layer=per_layer)
    if "fill_pods" in config.get("probe", {}):
        _hold_the_probes_fill(cell)
    return cell


def _hold_the_probes_fill(cell: Cell) -> None:
    """A configuration whose probe brings a fill of its own (``probe.
    fill_pods``): the window's pods, the batch-sized fill, that fill and the
    probes together, each at the largest size, stay under the cluster's cpu,
    memory and pod slots, or the cell does not load."""
    sizes, nodes, probe = cell.sizes(), cell.config["nodes"], cell.config["probe"]
    own = int(probe["fill_pods"])
    if own < 1:
        raise SystemExit(f"cell {cell.name}: probe.fill_pods is {own}; a fill "
                         "is at least one pod, and no fill is no key")
    pods = sizes["resident_pods"] + 2 * sizes["batch_pods"] + own \
        + int(probe["probes"])
    pod = cell.config["pods"]
    room = {"cpu": (max(pod["cpu_choices"]), int(nodes["cpu"])),
            "memory_gi": (max(pod["mem_gi_choices"]), int(nodes["memory_gi"])),
            "pods": (1, int(nodes["pods"]))}
    for what, (each, a_node) in room.items():
        if pods * each >= int(nodes["count"]) * a_node:
            raise SystemExit(
                f"cell {cell.name}: probe.fill_pods {own}: the window's "
                f"{sizes['resident_pods'] + sizes['batch_pods']} pods, the "
                f"fill's {sizes['batch_pods']}, the probe's own {own} and its "
                f"{probe['probes']} probes are {pods * each} {what} of the "
                f"cluster's {int(nodes['count']) * a_node}")
