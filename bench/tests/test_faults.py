"""The output check fails runs whose timed path is broken underneath: the
whole run (data, program, window, reference, comparison) at a tiny size on
the CPU, with one fault planted in the program, must come out not correct.
Faults: a step that returns its state unchanged (zero gradients); half of
the batch left out of the loss, the mean taken over the rest; one answer
altered where it is produced. One chip, so no exchange between chips can be
left out. The control, the reference computed in bfloat16 in the program's
place, must fail the check too; so must the float8 reading kept beside it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import run
from _tiny import SEED, cells, tiny_run, workload, workloads

harness.use_program()
from repro.core import engine as engine_mod  # noqa: E402
from repro.runtime import forward as forward_mod  # noqa: E402

TRAIN = [w["name"] for w in workloads()
         if harness.load_json("workloads", w["traffic"] + ".json")["job"]
         == "train"]


@pytest.mark.parametrize("cell", TRAIN)
def test_unchanged_state_fails(cell, monkeypatch):
    real = engine_mod.SSOEngine.run_epoch

    def run_epoch(self, params, labels):
        loss, grads = real(self, params, labels)
        return loss, jax.tree.map(np.zeros_like, grads)

    monkeypatch.setattr(engine_mod.SSOEngine, "run_epoch", run_epoch)
    rec = tiny_run(cell)
    assert not rec["correct"]
    assert rec["checks"]["grad"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", TRAIN)
def test_half_batch_fails(cell, monkeypatch):
    real = engine_mod.loss_and_grad

    def half(logits, labels, n_total):
        keep = jnp.arange(labels.shape[0]) % 2 == 0
        return real(logits, jnp.where(keep, labels, -1), n_total / 2)

    monkeypatch.setattr(engine_mod, "loss_and_grad", half)
    rec = tiny_run(cell)
    assert not rec["correct"]


@pytest.mark.parametrize("cell", cells())
def test_altered_answer_fails(cell, monkeypatch):
    real = forward_mod.ForwardRunner.run_layer

    def run_layer(self, l, params_l, activate, after_compute=None,
                  out_name=None):
        real(self, l, params_l, activate, after_compute, out_name)
        if l == len(self.dims) - 2:     # the final layer: alter one node
            name = out_name or self.act_name(l + 1)
            row = self.storage.read_rows(name, 3, 4)
            self.storage.write_rows(name, 3, row + 1.0)

    monkeypatch.setattr(forward_mod.ForwardRunner, "run_layer", run_layer)
    rec = tiny_run(cell)
    assert not rec["correct"]
    assert rec["checks"]["out_row"]["value"] > \
        rec["checks"]["out_row"]["limit"]


def _control_check(cell: str, control: str):
    """The check of ``bench/run.py`` with the control, computed on the
    cell's data, in the program's place."""
    wl = workload(cell)
    cfg = harness.load_json("configs", wl["config"] + ".json")
    cfg = {**cfg, **cfg["rehearse"]}
    c = harness.Cell(cell, cfg,
                     harness.load_json("workloads", wl["traffic"] + ".json"))
    c.load(SEED)
    limits = harness.load_json("limits", cell + ".json")["limits"]
    return run.check(c.reference(control=control), c.reference(), limits)


@pytest.mark.parametrize("cell", TRAIN)
def test_bf16_control_fails(cell):
    ok, checks = _control_check(cell, "bf16")
    assert not ok, checks
    assert checks["loss"]["value"] > checks["loss"]["limit"], checks


@pytest.mark.parametrize("cell", cells())
def test_fp8_control_fails(cell):
    ok, checks = _control_check(cell, "fp8")
    assert not ok, checks
