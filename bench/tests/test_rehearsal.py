"""Every cell rehearsed on the CPU at a tiny size, traced and not: the run
is correct, compiles nothing in its window, every metric module that
``BENCHMARK.json`` declares for the cell reads a value (device metrics read
nothing off a TPU), and every module under ``bench/metrics`` runs on the
run's record."""
import glob
import os

import pytest

import harness
from _tiny import cells, tiny_run, workload

DEVICE_METRICS = ("roofline", "mfu", "idle")


def test_every_declared_file_exists():
    bm = harness.benchmark()
    for c in bm["configs"]:
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
    for w in bm["workloads"] + [workload(c) for c in cells()]:
        for parts in (("workloads", w["traffic"] + ".json"),
                      ("limits", w["name"] + ".json")):
            assert os.path.exists(os.path.join(harness.BENCH, *parts))
        lim = harness.load_json("limits", w["name"] + ".json")
        assert (lim["config"], lim["traffic"]) == (w["config"], w["traffic"])
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert os.path.exists(
            os.path.join(harness.BENCH, "metrics", m["name"] + ".py"))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", cells())
def test_tiny_run(cell, trace):
    rec = tiny_run(cell, trace)
    assert rec["correct"], rec["checks"]
    assert rec["window_compiles"] == 0 and rec["iters"] >= 1
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in
                harness.cell_metrics(harness.benchmark(), cell, kind)}
    got = set(rec["metrics"])
    assert got <= declared
    missing = {m for m in declared - got
               if not any(k in m for k in DEVICE_METRICS)}
    assert not missing
    assert not any(any(k in m for k in DEVICE_METRICS) for m in got)
    for path in glob.glob(os.path.join(harness.BENCH, "metrics", "*.py")):
        name = os.path.basename(path)[:-3]
        value = harness.load_module("metrics", name + ".py").read(rec)
        assert value is None or value >= 0, (name, value)
