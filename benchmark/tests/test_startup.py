"""The four ``startup_*`` readers on hand-made facts: the parent's facts
(a ``Metrics`` snapshot with no ``process``) give None, and so does a
record that never closed; a closed record gives its numbers.

    python3 -m pytest benchmark/tests/test_startup.py -q    (not tier-1)
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import common, run  # noqa: E402
from benchmark.reduce import startup  # noqa: E402

NAMES = ("startup_ready_s", "startup_compile_s", "startup_cache_misses", "startup_manager_s")
CELLS = [
    "gpt2s-ft1", "gpt2m-ft1", "olmoe-ft1", "mellum2-ft1", "ouro-ft1", "sdar-ft1",
    "ling3-ft1", "dsv2lite-ft1",
]


def _one(seconds):
    return {"n": 1, "total_s": seconds, "p50": seconds, "p90": seconds, "max": seconds}


def _facts(process):
    metrics = {"counters": {"commits": 300}, "timers_s": {"quorum": _one(0.001)}, "events": {}}
    if process is not None:
        metrics["process"] = process
    return {"manager_metrics": metrics}


RECORD = {
    "counters": {"compiles": 9, "compile_cache_hits": 7, "compile_cache_misses": 2,
                 "startup_cache_hits": 4, "startup_cache_misses": 2},
    "timers_s": {
        "compile": {"n": 9, "total_s": 40.0, "p50": 1.0, "p90": 20.0, "max": 20.0},
        "spawn_to_import": _one(0.06), "import_to_manager": _one(25.0),
        "manager_init": _one(0.2), "first_quorum": _one(0.04), "heal": _one(0.0),
        "first_step": _one(0.7), "ready": _one(26.0), "startup_compile": _one(31.5),
    },
}
OPEN = {"counters": {"compiles": 3}, "timers_s": {"compile": _one(0.5)}}  # no commit yet


def _read(facts):
    wanted = [{"name": name, "unit": "s"} for name in NAMES]
    return {k: v["value"] for k, v in run.read_metrics("layer_metrics", wanted, facts).items()}


@pytest.mark.parametrize("process", [None, OPEN, {}], ids=["parent", "open", "empty"])
def test_no_record_no_metric(process):
    assert startup.record(_facts(process)) is None
    assert _read(_facts(process)) == {}
    assert _read({}) == {} and _read({"manager_metrics": None}) == {}


def test_a_closed_record_gives_its_numbers():
    got = _read(_facts(RECORD))
    assert got == {
        "startup_ready_s": 26.0, "startup_compile_s": 31.5,
        "startup_cache_misses": 2.0, "startup_manager_s": pytest.approx(0.24),
    }
    warm = json.loads(json.dumps(RECORD))
    del warm["counters"]["startup_cache_misses"]  # a counter never touched is absent
    assert _read(_facts(warm))["startup_cache_misses"] == 0.0  # reported, as 0


def test_the_entries_are_the_last_four_and_name_the_managers_cells():
    with open(os.path.join(common.REPO, "BENCHMARK.json")) as f:
        contract = json.load(f)
    last = contract["per_layer"][-4:]
    assert tuple(m["name"] for m in last) == NAMES
    for m in last:
        assert m["workloads"] == CELLS and m["moves"] == "setup_s"
        assert (m["layer"], m["better"]) == ("entry / placement", "lower")
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.isfile(os.path.join(common.BENCH, "layer_metrics", m["name"] + ".py"))
    # the cells that build a Manager: every one on the ft-sync generator
    managers = [w["name"] for w in contract["workloads"] if w["traffic"].startswith("ft-sync")]
    assert managers == CELLS
    assert "workloads" not in next(m for m in contract["end_to_end"] if m["name"] == "setup_s")


def test_the_programs_own_snapshot_is_what_the_reader_reads():
    """A record closed by the program itself, through JSON as a run file
    carries it."""
    from torchft_tpu.metrics import Metrics
    from torchft_tpu.startup import StartupRecord

    record = StartupRecord(started=100.0, imported=100.5)
    record.compiled("jit(step)", 2.0)
    record.cache(hit=False)
    manager = Metrics()
    record.bind(manager)
    manager.record("quorum", 0.25)
    assert record.close(manager).startswith("ready in ")
    facts = {"manager_metrics": json.loads(json.dumps(manager.snapshot()))}
    got = _read(facts)
    assert got["startup_compile_s"] == 2.0 and got["startup_cache_misses"] == 1.0
    assert got["startup_manager_s"] == pytest.approx(0.25)
    assert got["startup_ready_s"] > 0.5
