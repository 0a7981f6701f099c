#!/usr/bin/env python3
"""Readings that the output check's limits are set from, for one cell, on
the chip, in one process:

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 [--control 3]

For each seed, one epoch or pass of the timed path (the same ``Cell`` as
``bench/run.py``, at the cell's own size) against the plain reference: the
lower readings. For the first ``--control`` seeds also

- the controls: the reference computed in bfloat16 (the step below the
  configurations' precision) and, for the record, in float8 in the
  program's place;
- training: half of the nodes left out of the loss, the mean taken over
  the rest (the reference with those loss weights in the program's place);
- one node's output altered where it is produced (replaced by another
  node's).

A step that returns its state unchanged (zero gradients) reads 1 on
``grad`` by the measure itself and needs no run. Prints one JSON line per
seed and reading, then the largest lower and smallest upper reading of each
number.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import harness


def _altered(got: dict, seed: int) -> dict:
    """``got`` with one node's output replaced by another node's."""
    rng = np.random.default_rng(seed)
    i, j = rng.choice(got["out"].shape[0], 2, replace=False)
    out = got["out"].copy()
    out[i] = out[j]
    return {**got, "out": out}


def _leaf_gaps(got: dict, ref: dict):
    """Per parameter leaf: its reference norm and relative gap."""
    if "grads" not in ref:
        return None
    import jax

    return [[float(np.linalg.norm(b)),
             float(np.linalg.norm(np.asarray(a, np.float64) - b)
                   / max(np.linalg.norm(b), 1e-30))]
            for a, b in zip(jax.tree.leaves(got["grads"]),
                            jax.tree.leaves(ref["grads"]))]


def calibrate(cell, seeds, n_control: int, limits: dict):
    """Readings of ``cell`` on ``seeds``; each line also says whether the
    reading passes ``limits``, the cell's committed ones."""
    lower, upper = {}, {}

    def emit(kind, seed, nums, **extra):
        ok = all(nums.get(k, float("inf")) <= v for k, v in limits.items())
        print(json.dumps(dict(kind=kind, seed=seed, correct=ok, **nums,
                              **extra)), flush=True)

    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        cell.load(seed)
        cell.open()
        if k == 0:
            cell.compile()
        cell.step()
        got = cell.outputs()
        cell.close()
        t1 = time.perf_counter()
        ref = cell.reference()
        nums = harness.compare(got, ref)
        emit("program", seed, nums, step_s=t1 - t0,
             reference_s=time.perf_counter() - t1, leaves=_leaf_gaps(got, ref))
        for n, v in nums.items():
            lower[n] = max(lower.get(n, 0.0), v)
        if k >= n_control:
            continue
        readings = {"control_fp8": cell.reference(control="fp8"),
                    "control_bf16": cell.reference(control="bf16"),
                    "fault_altered_answer": _altered(got, seed)}
        if cell.job == "train":
            w = np.zeros(cell.n, np.float32)
            half = np.random.default_rng(seed).permutation(cell.n)[
                : cell.n // 2]
            w[half] = 1.0 / half.size
            readings["fault_half_batch"] = cell.reference(node_w=w)
        for kind, r in readings.items():
            nums = harness.compare(r, ref)
            emit(kind, seed, nums)
            for n, v in nums.items():
                upper.setdefault(kind, {})
                upper[kind][n] = min(upper[kind].get(n, np.inf), v)
    print(json.dumps(dict(kind="summary", lower=lower, upper=upper)),
          flush=True)
    return lower, upper


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.set_compile_cache()
    bm = harness.benchmark()
    wl = next(w for w in bm["workloads"] if w["name"] == args.workload)
    cell = harness.Cell(args.workload,
                        harness.load_json("configs", wl["config"] + ".json"),
                        harness.load_json("workloads", wl["traffic"] + ".json"))
    calibrate(cell, seeds, args.control,
              harness.load_json("limits", args.workload + ".json")["limits"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
