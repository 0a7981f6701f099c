"""The launchers build the SSO workload from the arch, and ``chip_smoke.py``
runs its phases and checks off the chip only as a rehearsal."""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import REGISTRY
from repro.launch.compile_cache import CACHE_DIR, CHECKOUT
from repro.launch.infer import _infer_smoke
from repro.launch.train import gnn_workload, offload_train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("arch,model,dims", [
    ("gcn-cora", "gcn", [1433, 16, 7]),
    ("graphsage-reddit", "sage", [602, 128, 41]),
    ("gcn-igbm-3l", "gcn", [1024, 256, 256, 19]),
])
def test_workload_takes_model_and_widths_from_arch(arch, model, dims):
    wl = gnn_workload(REGISTRY[arch].gnn, n_nodes=96, n_parts=4)
    assert wl.spec.name == model
    assert wl.dims == dims
    assert wl.n_nodes == 96 and wl.X.shape == (96, dims[0])
    assert len(wl.params) == len(dims) - 1


@pytest.mark.parametrize("arch", ["gcn-cora", "graphsage-reddit"])
def test_offload_train_pipelined_matches_serial(arch):
    wl = gnn_workload(REGISTRY[arch].gnn, n_nodes=96, n_parts=4)
    r = offload_train(wl, depths=(0, 2), epochs=1, warmup=1)
    assert r["finite"] and r["pipeline_matches_serial"]
    for run in r["runs"].values():
        assert len(run["losses"]) == 2 and len(run["epoch_s"]) == 1
        assert run["compiles"] == 0          # the warm-up compiled all
        assert run["storage_read_bytes"] > 0
        assert run["cache_pins"] == 0 and run["pool_outstanding"] == 0
        assert run["logits"].shape == (96, wl.dims[-1])


@pytest.mark.parametrize("arch", ["gcn-cora", "graphsage-reddit"])
def test_infer_launcher_runs_arch(arch):
    wl = gnn_workload(REGISTRY[arch].gnn, n_nodes=96, n_parts=4)
    r = _infer_smoke(wl, 2, queries=2, batch=16)
    assert r["finite"] and r["pipeline_matches_serial"]
    assert r["serve_matches_dense"]


def test_compile_cache_path_is_fixed_and_ignored():
    assert CHECKOUT == REPO
    assert CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_dryrun_forces_cpu_before_jax_import():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c",
         "import repro.launch.dryrun, jax; print(jax.default_backend())"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[-1] == "cpu"


@pytest.fixture()
def chip_smoke(monkeypatch, tmp_path):
    # with the variable set, the smoke's cache helper sets nothing; JAX read
    # it at import, so the persistent cache stays off in this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    before = jax.config.jax_compilation_cache_dir
    yield chip_smoke
    assert jax.config.jax_compilation_cache_dir == before


def test_chip_smoke_refuses_off_the_chip(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_chip_smoke_rehearsal_runs_every_phase_and_check(chip_smoke, capsys):
    assert chip_smoke.main(["--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    for phase in ("[device]", "[build]", "[train] depth=0",
                  "[train] depth=2", "[infer]"):
        assert any(l.startswith(phase) for l in lines), phase
    checks = [l for l in lines if l.startswith("[check]")]
    for name in ("loss_finite", "depth2_equals_depth0_bitwise",
                 "cache_below_working_set", "storage_bytes_read",
                 "no_compiles_in_timed_epochs", "pins_and_pool_returned",
                 "sso_matches_whole_graph",
                 "inference_bit_identical_to_training_forward",
                 "peak_device_memory"):
        assert any(f"[check] {name}: PASS" in l for l in checks), name
    last = json.loads(lines[-1])
    assert "ok" not in last and last["rehearsal_ok"] is True
    assert last["device"]["platform"] == jax.devices()[0].platform
    assert not any('"ok": true' in l for l in lines)
