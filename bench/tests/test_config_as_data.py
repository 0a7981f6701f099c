"""A configuration is data: its rehearsal size is in its own file, and its
model module is told each layer's position. A configuration that no Python
file names, added to a copy of ``bench/`` as files alone, rehearses as a
pending cell and comes out correct."""
import glob
import inspect
import json
import os
import shutil

import jax
import numpy as np
import pytest

import harness
from _tiny import tiny_run

CONFIGS = sorted(glob.glob(os.path.join(harness.BENCH, "configs", "*.json")))
MODELS = sorted(os.path.basename(p)[:-3] for p in
                glob.glob(os.path.join(harness.BENCH, "models", "*.py")))


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_config_declares_rehearse(path):
    with open(path) as f:
        rehearse = json.load(f)["rehearse"]
    assert set(rehearse) == {"n_nodes", "n_parts"}
    assert all(isinstance(v, int) and v > 0 for v in rehearse.values())
    assert rehearse["n_parts"] < rehearse["n_nodes"]


@pytest.mark.parametrize("model", MODELS)
def test_model_takes_position(model):
    mod = harness.load_module("models", model + ".py")
    for fn in (mod.init, mod.model_flops, mod.forward, mod.backward,
               mod.fwd_cost, mod.bwd_cost):
        assert "activate" in inspect.signature(fn).parameters, fn.__name__


@pytest.mark.parametrize("model", ["gcn", "sage"])
def test_position_leaves_weights_and_flops(model):
    """gcn and sage layers have one tree and one FLOP count at every
    position: the same weights, bit for bit, and the same model FLOPs."""
    mod = harness.load_module("models", model + ".py")
    key = jax.random.key(7)
    hidden, out = mod.init(key, 24, 16, True), mod.init(key, 24, 16, False)
    assert jax.tree.structure(hidden) == jax.tree.structure(out)
    for a, b in zip(jax.tree.leaves(hidden), jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert (mod.model_flops(300, 4000, 24, 16, True)
            == mod.model_flops(300, 4000, 24, 16, False))


PROBE = "probe-sage-3l"
PROBE_CONFIG = {
    "name": PROBE, "model": "sage",
    "n_layers": 3, "d_hidden": 128, "d_feat": 602, "classes": 41,
    "n_nodes": 16384, "avg_degree": 492, "n_parts": 16, "graph_seed": 0,
    "rehearse": {"n_nodes": 256, "n_parts": 4},
}


def test_config_added_as_files_rehearses(tmp_path, monkeypatch, capsys):
    """A copy of ``bench/`` gains a configuration and its cell's limits
    file, and nothing else: the pending cell rehearses correct, with no
    compile in its window."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    cell = PROBE + "-train-tight"
    (bench / "configs" / (PROBE + ".json")).write_text(
        json.dumps(PROBE_CONFIG))
    (bench / "limits" / (cell + ".json")).write_text(json.dumps(
        {"config": PROBE, "traffic": "train-tight",
         "limits": {"out_row": 0.025, "loss": 3e-5, "grad": 0.085}}))
    for path in bench.rglob("*.py"):
        assert PROBE not in path.read_text(), path
    cache = tmp_path / "cache"
    monkeypatch.setattr(harness, "BENCH", str(bench))
    monkeypatch.setattr(harness, "CACHE", str(cache))
    monkeypatch.setattr(harness, "STORAGE_ROOT", str(cache / "storage"))

    rec = tiny_run(cell)
    assert "dims=[602, 128, 128, 41]" in capsys.readouterr().out
    assert rec["correct"], rec["checks"]
    assert rec["window_compiles"] == 0 and rec["iters"] >= 1
    assert set(rec["checks"]) == {"out_row", "loss", "grad"}
