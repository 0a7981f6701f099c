"""Fault-tolerance tests: injection harness, checksummed blocks,
retry/backoff, graceful pipeline unwind, and crash-consistent recovery.

The acceptance property (mirrors benchmarks/fault_soak.py): with every
injected fault transient, a pipelined engine run (depth >= 1, sharded
gathers, transfer stage on) produces loss/grads BIT-IDENTICAL to a
fault-free serial run, with the recovery work visible in the metrics.
Unrecoverable faults must raise typed errors within bounded wall-clock,
releasing every pooled buffer and cache pin on the way out.
"""
import gc
import logging
import os
import signal
import subprocess
import sys
import tempfile
import textwrap
import time
import traceback

import jax
import numpy as np
import pytest

from repro.core import Counters, HostCache, SSOEngine, StorageTier, build_plan
from repro.core.faults import FaultPolicy, FaultyTier
from repro.core.storage import (
    RetryPolicy, StorageCorruptionError, StorageDeadlineError, StorageError,
    StorageFullError, StorageIOQueue, TransientIOError,
)
from repro.graph import (
    gcn_norm_coeffs, kronecker_graph, switching_aware_partition,
)
from repro.graph.csr import add_self_loops
from repro.graph.synthetic import random_features, random_labels
from repro.models.gnn.layers import get_gnn
from repro.runtime import PipelineConfig
from repro.runtime.executor import PipelineExecutor

_FAST_RETRY = RetryPolicy(max_retries=8, backoff_s=1e-4, backoff_max_s=1e-3,
                          op_deadline_s=5.0)


def _metric(c, name):
    inst = c.metrics.get(name)
    return float(inst.value) if inst is not None else 0.0


# ------------------------------------------------------------- fault policy
def test_fault_policy_deterministic_per_seed():
    kw = dict(read_error_rate=0.3, write_error_rate=0.2,
              read_corrupt_rate=0.15, torn_write_rate=0.1,
              latency_spike_rate=0.1)
    a, b = FaultPolicy(seed=7, **kw), FaultPolicy(seed=7, **kw)
    seq_a = [a.draw(k) for k in (["read"] * 50 + ["write"] * 50)]
    seq_b = [b.draw(k) for k in (["read"] * 50 + ["write"] * 50)]
    assert seq_a == seq_b
    assert a.injected == b.injected
    c = FaultPolicy(seed=8, **kw)
    seq_c = [c.draw(k) for k in (["read"] * 50 + ["write"] * 50)]
    assert seq_c != seq_a


def test_fault_policy_schedule_and_budget():
    p = FaultPolicy(seed=0, max_faults=1)
    p.schedule("write", 2, "torn").schedule("read", 0, "error")
    assert p.draw("read") == ["error"]        # scheduled, attempt-indexed
    assert p.draw("write") == []
    assert p.draw("write") == []
    assert p.draw("write") == ["torn"]        # write attempt #2
    with pytest.raises(ValueError):
        p.schedule("read", 0, "torn")         # torn is write-only


# --------------------------------------------------- checksums + detection
def test_crc_roundtrip_and_persistent_corruption(rng):
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c, verify_reads=True,
                      retry=_FAST_RETRY)
    arr = rng.standard_normal((16, 4)).astype(np.float32)
    st_.alloc("x", (16, 4))
    st_.write_rows("x", 0, arr)
    np.testing.assert_array_equal(st_.read_rows("x", 0, 16), arr)
    # flip a bit at rest (media corruption): the sidecar CRC no longer
    # matches, and re-reading can't help — typed fatal after the re-read
    st_._arrays["x"][3, 2] += 1.0
    with pytest.raises(StorageCorruptionError):
        st_.read_rows("x", 0, 16)
    assert _metric(c, "io.corruption_rereads") >= 1
    st_.close()


def test_crc_detects_torn_write_at_rest(rng):
    st_ = StorageTier(tempfile.mkdtemp(), verify_reads=True,
                      retry=_FAST_RETRY)
    old = rng.standard_normal((8, 4)).astype(np.float32)
    new = rng.standard_normal((8, 4)).astype(np.float32)
    st_.alloc("x", (8, 4))
    st_.write_rows("x", 0, old)
    # emulate a tear: CRCs recorded for `new`, but only half the rows land
    st_._record_crcs("x", 0, new)
    st_._arrays["x"][0:4] = new[0:4]
    with pytest.raises(StorageCorruptionError):
        st_.read_rows("x", 0, 8)
    st_.close()


def test_transient_read_corruption_recovers_bit_exact(rng):
    c = Counters()
    policy = FaultPolicy(seed=0).schedule("read", 0, "corrupt")
    st_ = FaultyTier(tempfile.mkdtemp(), policy=policy, counters=c,
                     retry=_FAST_RETRY)
    arr = rng.standard_normal((32, 8)).astype(np.float32)
    st_.alloc("x", (32, 8))
    st_.write_rows("x", 0, arr)
    np.testing.assert_array_equal(st_.read_rows("x", 0, 32), arr)
    assert _metric(c, "io.corruption_rereads") == 1
    assert policy.n_injected == 1
    st_.close()


# -------------------------------------------------------- retry + deadline
def test_transient_errors_retried_with_count(rng):
    c = Counters()
    policy = FaultPolicy(seed=0)
    policy.schedule("read", 0, "error").schedule("read", 1, "error")
    st_ = FaultyTier(tempfile.mkdtemp(), policy=policy, counters=c,
                     retry=_FAST_RETRY)
    arr = rng.standard_normal((8, 4)).astype(np.float32)
    st_.alloc("x", (8, 4))
    st_.write_rows("x", 0, arr)
    np.testing.assert_array_equal(st_.read_rows("x", 0, 8), arr)
    assert _metric(c, "io.retries") == 2
    assert _metric(c, "io.faults_injected") == 2
    st_.close()


def test_torn_write_retried_to_full_write(rng):
    c = Counters()
    policy = FaultPolicy(seed=0).schedule("write", 0, "torn")
    st_ = FaultyTier(tempfile.mkdtemp(), policy=policy, counters=c,
                     retry=_FAST_RETRY)
    arr = rng.standard_normal((8, 4)).astype(np.float32)
    st_.alloc("x", (8, 4))
    st_.write_rows("x", 0, arr)       # torn attempt, then clean retry
    np.testing.assert_array_equal(st_.read_rows("x", 0, 8), arr)
    assert _metric(c, "io.retries") >= 1
    st_.close()


def test_retry_exhaustion_raises_deadline_error(rng):
    c = Counters()
    st_ = FaultyTier(
        tempfile.mkdtemp(), policy=FaultPolicy(seed=0, read_error_rate=1.0),
        counters=c,
        retry=RetryPolicy(max_retries=3, backoff_s=1e-4, backoff_max_s=1e-3,
                          op_deadline_s=0.5),
    )
    st_.alloc("x", (8, 4))
    st_.write_rows("x", 0, np.zeros((8, 4), np.float32))
    t0 = time.perf_counter()
    with pytest.raises(StorageDeadlineError):
        st_.read_rows("x", 0, 8)
    assert time.perf_counter() - t0 < 2.0
    assert _metric(c, "io.deadline_misses") >= 1
    st_.close()


def test_enospc_is_fatal_not_retried():
    c = Counters()
    policy = FaultPolicy(seed=0).schedule("write", 0, "enospc")
    st_ = FaultyTier(tempfile.mkdtemp(), policy=policy, counters=c,
                     retry=_FAST_RETRY)
    st_.alloc("x", (8, 4))
    with pytest.raises(StorageFullError):
        st_.write_rows("x", 0, np.zeros((8, 4), np.float32))
    assert _metric(c, "io.retries") == 0
    st_.close()


def test_no_retry_policy_propagates_transient():
    policy = FaultPolicy(seed=0).schedule("read", 0, "error")
    st_ = FaultyTier(tempfile.mkdtemp(), policy=policy, retry=None,
                     verify_reads=False)
    st_.alloc("x", (4, 4))
    st_.write_rows("x", 0, np.zeros((4, 4), np.float32))
    with pytest.raises(TransientIOError):
        st_.read_rows("x", 0, 4)
    st_.close()


# ------------------------------------------------- I/O queue observability
def test_io_queue_deadline_observation():
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    q = StorageIOQueue(st_, counters=c, op_deadline_s=1e-9)
    st_.alloc("x", (8, 4))
    q.submit_write("x", 0, np.zeros((8, 4), np.float32))
    q.drain()
    assert _metric(c, "io.deadline_misses") >= 1
    q.close()
    st_.close()


class _SleepyTier(StorageTier):
    sleep_s = 0.0

    def _write_rows_once(self, name, row0, arr):
        if self.sleep_s:
            time.sleep(self.sleep_s)
        super()._write_rows_once(name, row0, arr)


def test_slow_lane_flips_on_latency_spike_and_recovers():
    c = Counters()
    st_ = _SleepyTier(tempfile.mkdtemp(), counters=c)
    q = StorageIOQueue(st_, counters=c, slow_lane_factor=4.0,
                       slow_lane_min_ops=4, slow_lane_recovery_ops=3)
    st_.alloc("x", (64, 4))
    z = np.zeros((1, 4), np.float32)
    for i in range(8):                       # establish a fast EWMA
        q.submit_write("x", i, z)
    q.drain()
    assert not q.slow_lane
    st_.sleep_s = 0.05                       # one spiking op
    q.submit_write("x", 8, z)
    q.drain()
    assert q.slow_lane
    assert _metric(c, "io.slow_lane_flips") >= 1
    st_.sleep_s = 0.0                        # clean run of ops recovers
    for i in range(4):
        q.submit_write("x", 9 + i, z)
    q.drain()
    assert not q.slow_lane
    q.close()
    st_.close()


# ------------------------------------------------------ engine-level setup
def _setup(n_nodes=900, n_parts=5, d_in=16, seed=0):
    g = add_self_loops(kronecker_graph(n_nodes, 7, seed=seed))
    res = switching_aware_partition(g, n_parts, max_iters=8, seed=seed)
    plan = build_plan(g, res.parts, n_parts, edge_weight=gcn_norm_coeffs(g))
    X = random_features(g.n_nodes, d_in, seed)
    Y = random_labels(g.n_nodes, 8, seed)
    return plan, X[plan.ro.perm], Y[plan.ro.perm]


def _build_engine(plan, tier, c, dims, depth, gather_workers=1,
                  budget_kb=8192, **pkw):
    spec = get_gnn("gcn")
    params = spec.init(jax.random.PRNGKey(0), dims[0], dims[1], dims[-1],
                       len(dims) - 1)
    cache = HostCache(budget_kb << 10, tier, c)
    eng = SSOEngine(
        spec, plan, dims, tier, cache, c, mode="regather",
        pipeline=PipelineConfig(depth=depth, gather_workers=gather_workers,
                                transfer_stage=True, **pkw),
    )
    return eng, cache, params


def _assert_trees_identical(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------ acceptance: bit-identity
def test_faulted_pipelined_epoch_bit_identical_to_clean_serial():
    """ISSUE acceptance: seeded transient faults (read+write errors >= 1%,
    a scheduled torn write, a scheduled latency spike) under a pipelined
    run (depth 2, 2 gather workers, transfer stage on) — final loss/grads
    bit-identical to the fault-free serial run, retries visible."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]

    c0 = Counters()
    st0 = StorageTier(tempfile.mkdtemp(), counters=c0)
    eng0, _, params = _build_engine(plan, st0, c0, dims, depth=0)
    eng0.initialize(Xr)
    l0, g0 = eng0.run_epoch(params, Yr)
    eng0.close()
    st0.close()

    policy = FaultPolicy(
        seed=1, read_error_rate=0.01, write_error_rate=0.01,
        read_corrupt_rate=0.005, latency_spike_rate=0.002,
        latency_spike_s=0.001,
    )
    policy.schedule("write", 3, "torn")
    policy.schedule("read", 2, "latency")
    policy.schedule("read", 4, "error")
    c1 = Counters()
    st1 = FaultyTier(tempfile.mkdtemp(), policy=policy, counters=c1,
                     verify_reads=True, retry=_FAST_RETRY)
    eng1, cache1, params1 = _build_engine(plan, st1, c1, dims, depth=2,
                                          gather_workers=2)
    eng1.initialize(Xr)
    l1, g1 = eng1.run_epoch(params1, Yr)
    eng1.close()
    st1.close()

    assert l0 == l1
    _assert_trees_identical(g0, g1)
    assert policy.n_injected >= 3
    assert _metric(c1, "io.retries") > 0
    assert _metric(c1, "io.faults_injected") == policy.n_injected


def test_unrecoverable_fault_unwinds_engine_cleanly():
    """A fatal (non-retryable) storage fault mid-epoch: run_epoch raises the
    typed error within bounded wall-clock, every cache pin and pooled
    buffer is released, and close() still terminates."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]
    policy = FaultPolicy(seed=0).schedule("read", 2, "enospc")
    c = Counters()
    st_ = FaultyTier(tempfile.mkdtemp(), policy=policy, counters=c,
                     retry=_FAST_RETRY)
    eng, cache, params = _build_engine(plan, st_, c, dims, depth=2,
                                       gather_workers=2)
    eng.initialize(Xr)        # writes only — the scheduled read fault
    t0 = time.perf_counter()  # fires inside the epoch's prefetch/gather
    with pytest.raises(StorageError):
        eng.run_epoch(params, Yr)
    assert time.perf_counter() - t0 < 30.0
    assert cache.total_pins == 0
    gc.collect()
    assert eng.fwd_runner._rt.pool.outstanding == 0
    t0 = time.perf_counter()
    eng.close()
    assert time.perf_counter() - t0 < 10.0
    st_.close()


class _NthReadFails(StorageTier):
    """Fails the ``fail_at``-th read of file ``name`` with a fatal
    (non-retryable) error, kept as ``raised``."""

    def __init__(self, root, name, fail_at, **kw):
        super().__init__(root, **kw)
        self.fail_name, self.fail_at = name, fail_at
        self.reads, self.raised = 0, None

    def _read_rows_once(self, name, row0, row1):
        if name == self.fail_name:
            self.reads += 1
            if self.reads == self.fail_at:
                self.raised = StorageFullError(f"{name}: read {self.reads}")
                raise self.raised
        return super()._read_rows_once(name, row0, row1)


def test_retired_scatter_fault_surfaces_from_layer_drain():
    """A fatal storage error inside a scatter on the retire thread: the
    degraded write-back (cache too small for any ∇A block) reads ∇A^1 back
    before each add after a partition's first, and the last of those reads,
    in the layer's last unit, fails. The compute loop has handed every unit
    over by then, so the layer's drain raises the original exception; pins
    return to zero and the engine closes."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]
    units = [plan.unit(p) for p in plan.schedule]
    seen, rmw = set(), []
    for u in units:
        parts = {int(q) for q in u.req_parts}
        rmw.append(len(parts & seen))
        seen |= parts
    assert rmw[-1] > 0      # the failing read is the last unit's
    c = Counters()
    st_ = _NthReadFails(tempfile.mkdtemp(), "grad1", sum(rmw), counters=c,
                        retry=_FAST_RETRY)
    eng, cache, params = _build_engine(plan, st_, c, dims, depth=2,
                                       budget_kb=4)
    eng.initialize(Xr)
    t0 = time.perf_counter()
    with pytest.raises(StorageFullError) as ei:
        eng.run_epoch(params, Yr)
    assert time.perf_counter() - t0 < 30.0
    assert ei.value is st_.raised
    frames = [f.name for f in traceback.extract_tb(ei.value.__traceback__)]
    assert "_scatter_unit" in frames and "drain_writes" in frames
    assert "retire" not in frames
    assert c.retired_scatter_units == len(units)
    assert cache.total_pins == 0
    gc.collect()
    assert eng.fwd_runner._rt.pool.outstanding == 0
    t0 = time.perf_counter()
    try:
        eng.close()
    except StorageError as e:   # the I/O queue keeps the failed read's error
        assert e is st_.raised
    assert time.perf_counter() - t0 < 10.0
    assert c.threads_leaked == 0
    st_.close()


# ------------------------------------------- deadlock regression per stage
@pytest.mark.parametrize("stage", ["prefetch", "gather", "aux", "transfer"])
def test_stage_exception_unwinds_run_stream(stage):
    """Inject a raise into each pipeline stage: run_stream must re-raise
    within bounded wall-clock with every pooled buffer back (no deadlock,
    no leak) — stranded in-flight units are returned via cleanup_fn."""
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    ex = PipelineExecutor(
        PipelineConfig(depth=2, gather_workers=2, transfer_stage=True),
        c, st_,
    )

    def prefetch(it):
        if stage == "prefetch" and it == 3:
            raise ValueError("boom")

    def gather(it):
        if stage == "gather" and it == 3:
            raise ValueError("boom")
        return ex.pool.acquire((8, 8), np.float32)

    def aux(it):
        if stage == "aux" and it == 3:
            raise ValueError("boom")
        return None

    def transfer(it, buf, aux_):
        if stage == "transfer" and it == 3:
            raise ValueError("boom")
        return buf, aux_

    def cleanup(it, buf, aux_):
        if isinstance(buf, np.ndarray):
            ex.pool.release(buf)

    t0 = time.perf_counter()
    with pytest.raises(ValueError, match="boom"):
        for it, buf, aux_ in ex.run_stream(
            range(8), gather, prefetch_fn=prefetch, aux_fn=aux,
            transfer_fn=transfer, cleanup_fn=cleanup,
        ):
            if isinstance(buf, np.ndarray):
                ex.pool.release(buf)
    assert time.perf_counter() - t0 < 15.0
    gc.collect()
    assert ex.pool.outstanding == 0
    assert c.threads_leaked == 0
    t0 = time.perf_counter()
    ex.close()
    assert time.perf_counter() - t0 < 10.0
    st_.close()


def test_wedged_thread_join_timeout_warns_and_counts(caplog):
    """A worker stuck past thread_join_timeout_s must not hang shutdown:
    the join times out, the leak is logged and counted."""
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    ex = PipelineExecutor(
        PipelineConfig(depth=2, gather_workers=2, transfer_stage=False,
                       thread_join_timeout_s=0.2),
        c, st_,
    )

    def gather(it):
        if it == 0:
            raise ValueError("boom")
        time.sleep(1.5)       # wedged well past the join timeout
        return None

    with caplog.at_level(logging.WARNING, logger="repro.runtime"):
        t0 = time.perf_counter()
        with pytest.raises(ValueError, match="boom"):
            for _ in ex.run_stream(range(4), gather):
                pass
        assert time.perf_counter() - t0 < 5.0
    assert c.threads_leaked >= 1
    assert any("leaked" in r.getMessage() for r in caplog.records)
    time.sleep(1.6)           # let the sleeper finish before teardown
    ex.close()
    st_.close()


# --------------------------------------------------- degradation: slow lane
def test_slow_lane_forces_prefetch_pinning():
    """With pin_prefetched=False a flagged slow lane flips prefetch to
    cache-resident (pinned) mode — fewer re-reads on the sick lane — and
    the math is unchanged."""
    plan, Xr, Yr = _setup()
    dims = [16, 24, 8]
    c0 = Counters()
    st0 = StorageTier(tempfile.mkdtemp(), counters=c0)
    eng0, _, params = _build_engine(plan, st0, c0, dims, depth=0)
    eng0.initialize(Xr)
    l0, g0 = eng0.run_epoch(params, Yr)
    eng0.close()
    st0.close()

    c1 = Counters()
    st1 = StorageTier(tempfile.mkdtemp(), counters=c1)
    eng1, _, params1 = _build_engine(plan, st1, c1, dims, depth=2,
                                     gather_workers=2, pin_prefetched=False,
                                     slow_lane_pin=True)
    eng1.initialize(Xr)
    eng1.fwd_runner._rt.writer.slow_lane = True   # as if EWMA flagged it
    l1, g1 = eng1.run_epoch(params1, Yr)
    eng1.close()
    st1.close()
    assert c1.slow_lane_pins > 0
    assert l0 == l1
    _assert_trees_identical(g0, g1)


# -------------------------------------------------- checkpoints + recovery
def _params(scale=1.0):
    return {"w": np.arange(8, dtype=np.float64) * scale}


def test_latest_checkpoint_skips_torn_save(tmp_path):
    from repro.train.checkpoint import latest_checkpoint, save_checkpoint

    d = str(tmp_path)
    p1 = save_checkpoint(d, 1, _params(1.0))
    p2 = save_checkpoint(d, 2, _params(2.0))
    os.remove(os.path.join(p2, "params.npz"))     # tear the newest save
    assert latest_checkpoint(d) == p1


def test_gc_sweeps_tmp_strays_and_torn_dirs(tmp_path):
    from repro.train.checkpoint import save_checkpoint

    d = str(tmp_path)
    stray = os.path.join(d, ".tmp_stranded")
    os.makedirs(stray)
    with open(os.path.join(stray, "params.npz"), "w") as f:
        f.write("partial")
    torn = os.path.join(d, "step_0000000005")
    os.makedirs(torn)
    with open(os.path.join(torn, "manifest.json"), "w") as f:
        f.write("{ not json")
    save_checkpoint(d, 7, _params())              # triggers _gc
    names = set(os.listdir(d))
    assert ".tmp_stranded" not in names
    assert "step_0000000005" not in names
    assert "step_0000000007" in names


def _quadratic_loop(ckpt_dir, epochs, epoch_hook=None, resume=True):
    """Tiny deterministic epoch loop: loss = |w|^2, SGD on w."""
    from repro.train.loop import EpochLoopConfig, run_epoch_loop

    def epoch_fn(p, e):
        if epoch_hook is not None:
            epoch_hook(e)
        return float((p["w"] ** 2).sum()), {"w": 2.0 * p["w"]}

    def update_fn(g, p, o):
        return {"w": p["w"] - 0.1 * g["w"]}, o

    return run_epoch_loop(
        EpochLoopConfig(epochs=epochs, ckpt_dir=ckpt_dir, ckpt_every=1),
        _params(), None, epoch_fn, update_fn, log_fn=lambda s: None,
        resume=resume,
    )


def test_epoch_loop_resumes_bit_identical_after_crash(tmp_path):
    """In-process crash: epoch_fn raises mid-run; a fresh loop resumes from
    the last epoch-boundary checkpoint and finishes bit-identical to an
    uninterrupted run."""
    ref, _, ref_losses = _quadratic_loop(None, 5)

    d = str(tmp_path)

    def bomb(e):
        if e == 3:
            raise RuntimeError("simulated crash")

    with pytest.raises(RuntimeError):
        _quadratic_loop(d, 5, epoch_hook=bomb)
    got, _, losses = _quadratic_loop(d, 5)        # resumes at epoch 3
    np.testing.assert_array_equal(got["w"], ref["w"])
    assert losses == ref_losses


_VICTIM = textwrap.dedent("""
    import sys, time
    import numpy as np
    from repro.train.loop import EpochLoopConfig, run_epoch_loop

    ckpt, mode = sys.argv[1], sys.argv[2]

    def epoch_fn(p, e):
        if mode == "hang" and e >= 2:
            print("READY", flush=True)     # parent SIGKILLs us here,
            time.sleep(120)                # mid-epoch, after ckpt(2)
        return float((p["w"] ** 2).sum()), {"w": 2.0 * p["w"]}

    def update_fn(g, p, o):
        return {"w": p["w"] - 0.1 * g["w"]}, o

    params = {"w": np.arange(8, dtype=np.float64)}
    params, _, losses = run_epoch_loop(
        EpochLoopConfig(epochs=5, ckpt_dir=ckpt, ckpt_every=1),
        params, None, epoch_fn, update_fn, log_fn=lambda s: None)
    np.save(ckpt + "/final.npy", params["w"])
""")


@pytest.mark.slow
def test_kill_mid_epoch_resume_bit_identical(tmp_path):
    """SIGKILL a training process mid-epoch; a restarted process resumes
    from the last atomic checkpoint and finishes bit-identical to a run
    that was never killed."""
    script = tmp_path / "victim.py"
    script.write_text(_VICTIM)
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")

    d_kill = str(tmp_path / "ckpt_kill")
    proc = subprocess.Popen(
        [sys.executable, str(script), d_kill, "hang"],
        stdout=subprocess.PIPE, text=True, env=env,
    )
    try:
        line = proc.stdout.readline()       # victim is inside epoch 2
        assert "READY" in line
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        assert proc.returncode != 0
        assert not os.path.exists(os.path.join(d_kill, "final.npy"))
        # restart (no hang): resumes from the epoch-2 boundary checkpoint
        subprocess.run(
            [sys.executable, str(script), d_kill, "run"],
            check=True, timeout=120, env=env,
        )
    finally:
        if proc.poll() is None:
            proc.kill()
    # uninterrupted reference
    d_ref = str(tmp_path / "ckpt_ref")
    subprocess.run(
        [sys.executable, str(script), d_ref, "run"],
        check=True, timeout=120, env=env,
    )
    got = np.load(os.path.join(d_kill, "final.npy"))
    ref = np.load(os.path.join(d_ref, "final.npy"))
    np.testing.assert_array_equal(got, ref)
