"""Tiny CPU runs of ``bench/run.py``: every phase of a run at the cells'
widths on the few hundred nodes that each configuration's ``rehearse`` key
names, with the look for a chip skipped. Besides
the cells of ``BENCHMARK.json``, every cell that has a limits file
(``bench/limits/<cell>.json``, naming its configuration and traffic) but no
entry there yet is rehearsed too."""
import glob
import os

import harness
import run

SEED = 2**31 + 17


def workloads():
    declared = harness.benchmark()["workloads"]
    names = {w["name"] for w in declared}
    pending = []
    for path in sorted(glob.glob(os.path.join(harness.BENCH, "limits",
                                              "*.json"))):
        name = os.path.basename(path)[:-5]
        if name not in names:
            lim = harness.load_json("limits", name + ".json")
            pending.append({"name": name, "config": lim["config"],
                            "traffic": lim["traffic"], "chips": 1})
    return declared + pending


def cells():
    return [w["name"] for w in workloads()]


def workload(cell: str) -> dict:
    return next(w for w in workloads() if w["name"] == cell)


def tiny_run(cell: str, trace: int = 0, seconds: float = 0.3):
    wl = workload(cell)
    config = harness.load_json("configs", wl["config"] + ".json")
    limits = harness.load_json("limits", cell + ".json")["limits"]
    return run.main(["--workload", cell, "--seed", str(SEED),
                     "--seconds", str(seconds), "--trace", str(trace)],
                    rehearse=dict(config=config["rehearse"], limits=limits,
                                  workload=wl))
