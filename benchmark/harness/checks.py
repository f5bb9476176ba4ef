"""A configuration's own guarantees, by name.

``guarantees.checks: ["<name>"]`` in a configuration file resolves to
``reference/<name>_ref.py`` under the benchmark's first path (or, where
that directory has none, this harness's own ``reference/``: a copy of a
configuration in another directory keeps its guarantee) and to one agreed
function in it,

    check(events, nodes, config) -> {count name: count}

over plain data: ``events`` the run's ``validate.RoundEvents`` (every round,
set-up and probe included; each with its plan), ``nodes`` ``{"names": [...],
"labels": [{...}, ...]}`` and ``config`` the configuration file as read.
Every count has the limit 0, is printed beside it and is added to
``failed``.  A reference imports nothing of the program; one that is loaded
from another directory than this repo's (the tests' toy cells) is loaded by
its path and can import nothing relative to itself.
"""

from __future__ import annotations

import importlib
import importlib.util
from pathlib import Path
from typing import Dict, List, Sequence

OWN = Path(__file__).resolve().parents[1] / "reference"


def names(config: dict) -> List[str]:
    guarantees = config.get("guarantees")
    return list(guarantees.get("checks", [])) if isinstance(guarantees, dict) else []


def path_of(home: Path, name: str) -> Path:
    path = Path(home) / "reference" / f"{name}_ref.py"
    return path if path.is_file() else OWN / f"{name}_ref.py"


def load(home: Path, name: str):
    path = path_of(home, name)
    if not path.is_file():
        raise SystemExit(f"guarantees.checks names {name!r}: no {path}")
    if path.resolve().parent == OWN:
        module = importlib.import_module(f"benchmark.reference.{name}_ref")
    else:
        spec = importlib.util.spec_from_file_location(
            f"benchmark_check_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    if not callable(getattr(module, "check", None)):
        raise SystemExit(f"{path} has no check(events, nodes, config)")
    return module.check


def run(home: Path, config: dict, events: Sequence, nodes: dict) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for name in names(config):
        for count, value in load(home, name)(events, nodes, config).items():
            out[count] = out.get(count, 0) + int(value)
    return out
