"""PR 50's two per-layer metrics of ``preempt-10k.evict``, added as files
and appended entries alone: ``whatif_kernel_calls`` (plan tries that passed
the host gate and ran ``victim_scores``) and ``whatif_tables_built`` (victim
tables built), both read by the ``record`` reader out of the cycle records'
``whatif`` block.  A cut of the cell, run on the CPU with every plan try
held to the per-gang construction of before the table
(``test_whatif_preempt.shadowed``), reads them; records of a program
without the counters read nothing and raise nothing."""

import json
from types import SimpleNamespace

import pytest

import test_benchmark_contract as contract
from benchmark import run as bench_run
from benchmark.harness import cell as cell_mod
from benchmark.harness import readers
from test_benchmark_preempt_config import CELL, NINE, cut  # noqa: F401
from test_whatif_preempt import shadowed  # noqa: F401  (a fixture)

ROOT = cell_mod.ROOT
TWO = {"whatif_kernel_calls": "whatif.kernel_calls",
       "whatif_tables_built": "whatif.tables_built"}


def _spec(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics"
                       / f"{name}.json").read_text())


@pytest.mark.parametrize("metric", TWO)
def test_the_counter_is_an_appended_entry_and_a_file_of_this_cell(metric):
    names = [m["name"] for m in contract.BENCH["per_layer"]]
    assert names.index(metric) > max(names.index(n) for n in NINE)
    entry = contract.BENCH["per_layer"][names.index(metric)]
    assert entry == {"name": metric, "unit": "count", "better": "lower",
                     "source": "program_counter", "layer": "what-if engine",
                     "moves": "backlog_to_bind_ms", "workloads": [CELL]}
    on_file = _spec(metric)
    assert on_file["reader"] == "record" and on_file["what"]
    assert on_file["args"] == {"key": TWO[metric], "reduce": "sum"}
    assert {k: on_file[k] for k in ("name", "unit", "layer", "moves")} \
        == {k: entry[k] for k in ("name", "unit", "layer", "moves")}
    for w in contract.BENCH["workloads"]:
        listed = metric in [m["name"] for m in
                            cell_mod.load_cell(w["name"]).per_layer]
        assert listed == (w["name"] == CELL), w["name"]


@pytest.mark.parametrize("metric", TWO)
def test_records_without_the_counter_read_nothing(metric):
    """The parent's ``whatif`` block has neither key: the line leaves the
    metric out."""
    block = {"gangs_tried": 3, "committed": 1, "rejected": 0, "victims": 8}
    rounds = [SimpleNamespace(records=[{"whatif": block}, {"whatif": None}])]
    obs = readers.Observed(rounds=rounds)
    assert readers.read_record(_spec(metric)["args"], obs) is None
    assert readers.read_record(_spec("whatif_gangs_tried")["args"], obs) == 3
    with_it = dict(block, **{TWO[metric].split(".")[1]: 2})
    rounds[0].records.append({"whatif": with_it})
    assert readers.read_record(_spec(metric)["args"], obs) == 2


def test_a_cut_of_the_cell_reads_both_and_plans_as_before(cut, shadowed, capsys):
    c = cell_mod.load_cell(CELL, cut)
    result = bench_run.run(c, seed=2**31 + 50, seconds=1.0, trace=True)
    out = capsys.readouterr().out
    assert result["correct"] is True and result["failed"] == 0, out[-4000:]
    read = {k: v["value"] for k, v in result["metrics"].items()}
    # the 16 waiting pods' gangs are of the lowest class: tried, and gated
    assert 0 < read["whatif_kernel_calls"] < read["whatif_gangs_tried"]
    assert 1 <= read["whatif_tables_built"] <= read["whatif_gangs_tried"]
    assert read["whatif_victims"] >= 8 and read["compiles_in_window"] == 0
    # every try of the run was held to the per-gang construction
    assert len(shadowed) >= read["whatif_gangs_tried"]
    assert {a for a, _uid, _n in shadowed} == {"preempt", "reclaim"}
    gated = [n for _a, _uid, n in shadowed if n is None]
    assert gated and sum(n for _a, _uid, n in shadowed if n) >= 8
