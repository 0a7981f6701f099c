"""Counters accounting tests: overlap_summary per-pass math (hand-computed),
locked snapshots under concurrent mutation, and the bounded memory timeline.

``overlap_summary`` drives the headline numbers benchmarks/pipeline_overlap.py
prints (paper Fig. 13), so its splits are pinned against hand-worked
arithmetic here — including the ``xfer_wait_up`` clamp that stops upstream
gather wait from being double-charged against the transfer stage.
"""
import threading

import pytest

from repro.core import Counters


def _stalled(c: Counters, items):
    for k, v in items.items():
        c.record_stall(k, v)


def _busy(c: Counters, items):
    for k, v in items.items():
        c.record_busy(k, v)


# ------------------------------------------------------------- overlap summary
def test_overlap_summary_hand_computed():
    c = Counters()
    _busy(c, {
        # forward stages
        "prefetch": 2.0, "gather": 3.0,
        # backward stages
        "regather": 1.5, "grad_fetch": 0.5,
        # transfer stages
        "h2d": 1.0, "d2h": 0.5,
        # shared I/O (blended totals only)
        "write_behind": 0.8,
    })
    _stalled(c, {
        "compute_wait_fwd": 0.5,
        "xfer_wait_up_fwd": 0.25,
        "compute_wait_bwd": 0.3,
        "compute_wait_loss": 0.1,
        "compute_wait_xfer_fwd": 0.6,
        "xfer_wait_up_loss": 0.05,
        "h2d.put": 0.2,              # queue stall: total only, not a wait
    })
    ov = c.overlap_summary(10.0)

    # busy = 2 + 3 + 1.5 + 0.5 + 1 + 0.5 + 0.8
    assert ov["busy_seconds"] == pytest.approx(9.3)
    # compute_wait* = 0.5 + 0.3 + 0.1 + 0.6
    assert ov["compute_wait_seconds"] == pytest.approx(1.5)
    # every stall, including the queue put
    assert ov["stall_seconds"] == pytest.approx(2.0)
    assert ov["overlapped_seconds"] == pytest.approx(9.3 - 1.5)
    assert ov["overlapped_frac"] == pytest.approx(7.8 / 10.0)

    # FWD: busy 5.0 minus (compute_wait_fwd 0.5 + xfer_wait_up_fwd 0.25)
    assert ov["overlapped_seconds_fwd"] == pytest.approx(4.25)
    assert ov["overlapped_frac_fwd"] == pytest.approx(0.425)
    # BWD: busy 2.0 minus (0.3 + 0.1 + xfer_wait_up_loss 0.05)
    assert ov["overlapped_seconds_bwd"] == pytest.approx(1.55)
    assert ov["overlapped_frac_bwd"] == pytest.approx(0.155)
    # XFER: busy 1.5 minus max(0, compute_wait_xfer 0.6 - xfer_wait_up 0.3)
    assert ov["overlapped_seconds_xfer"] == pytest.approx(1.2)
    assert ov["overlapped_frac_xfer"] == pytest.approx(0.12)


def test_overlap_summary_xfer_wait_up_clamp():
    """When the transfer thread's upstream wait exceeds the compute loop's
    chain-end wait, NO wait is attributable to the transfer stage — the
    clamp must not go negative and inflate the overlap."""
    c = Counters()
    _busy(c, {"h2d": 1.0})
    _stalled(c, {"compute_wait_xfer_fwd": 0.2, "xfer_wait_up_fwd": 0.9})
    ov = c.overlap_summary(4.0)
    assert ov["overlapped_seconds_xfer"] == pytest.approx(1.0)
    assert ov["overlapped_frac_xfer"] == pytest.approx(0.25)


def test_overlap_summary_never_negative_and_frac_capped():
    c = Counters()
    _busy(c, {"gather": 0.1})
    _stalled(c, {"compute_wait_fwd": 5.0})      # waits exceed busy
    ov = c.overlap_summary(0.05)
    assert ov["overlapped_seconds"] == 0.0
    assert ov["overlapped_frac"] == 0.0
    # frac is capped at 1.0 even for sub-wall windows
    c2 = Counters()
    _busy(c2, {"gather": 3.0})
    assert c2.overlap_summary(1.0)["overlapped_frac"] == 1.0
    # degenerate wall
    assert c2.overlap_summary(0.0)["overlapped_frac"] == 0.0


# --------------------------------------------------------------- snapshot lock
def test_snapshot_contains_flattened_maps():
    c = Counters()
    with c.stage("h2d"):
        pass
    c.record_busy("gather", 2.0)
    c.record_stall("compute_wait_fwd", 0.5)
    c.bump("storage_read_bytes", 123)
    snap = c.snapshot()
    # the busy/stall maps are the only flattened timings (no phase map)
    assert snap["busy_h2d"] >= 0.0
    assert not any(k.startswith("t_") for k in snap)
    assert snap["busy_gather"] == 2.0
    assert snap["stall_compute_wait_fwd"] == 0.5
    assert snap["storage_read_bytes"] == 123


def test_stage_and_wait_count_a_block_that_raises():
    """A faulted stage still counts its seconds and closes its span: the
    unwind of a failed epoch shows where the time went."""
    from repro.obs import Tracer

    c = Counters()
    c.tracer = Tracer()
    with pytest.raises(RuntimeError):
        with c.stage("gather", stream=1, seq=0):
            raise RuntimeError("storage fault")
    with pytest.raises(RuntimeError):
        with c.wait("compute_wait_fwd", stream=1, seq=0):
            raise RuntimeError("abort")
    assert c.stage_busy_seconds["gather"] >= 0.0
    assert "compute_wait_fwd" in c.stage_stall_seconds
    assert [e["name"] for e in c.tracer.events()] == [
        "gather", "stall:compute_wait_fwd"]
    assert c.tracer.current_unit() is None


def test_bump_is_atomic_under_contention():
    """Regression (engine ∇A write-back): the two host_scatter_bytes sites
    used a bare ``+=`` on the dataclass attribute — racy once gather workers
    and the main loop share the instance. ``bump`` must not lose updates."""
    c = Counters()
    n_threads, n_iters = 8, 5000
    start = threading.Barrier(n_threads)

    def _hammer():
        start.wait()
        for _ in range(n_iters):
            c.bump("host_scatter_bytes", 3)

    threads = [threading.Thread(target=_hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.host_scatter_bytes == 3 * n_threads * n_iters


def test_snapshot_consistent_under_concurrent_mutation():
    """snapshot() must hold the lock: worker threads mutate the stage maps
    while benches snapshot, and an unlocked read can see a dict mid-resize.
    Hammer both sides; any torn read raises inside snapshot()."""
    c = Counters()
    stop = threading.Event()
    errs = []

    def _mutate():
        i = 0
        while not stop.is_set():
            c.record_busy(f"stage{i % 50}", 0.001)
            c.record_stall(f"wait{i % 50}", 0.001)
            c.bump("cache_hits")
            i += 1

    def _snap():
        try:
            while not stop.is_set():
                s = c.snapshot()
                assert s["cache_hits"] >= 0
        except Exception as e:   # pragma: no cover - only on regression
            errs.append(e)

    threads = [threading.Thread(target=_mutate) for _ in range(2)]
    threads += [threading.Thread(target=_snap) for _ in range(2)]
    for t in threads:
        t.start()
    threading.Event().wait(0.3)
    stop.set()
    for t in threads:
        t.join()
    assert not errs


# ------------------------------------------------------------- memory timeline
def test_mem_timeline_decimates_at_cap_and_keeps_exact_peak():
    c = Counters()
    c.MEM_TIMELINE_CAP = 64          # instance attr shadows the class cap
    n = 1000
    for i in range(n):
        c.sample_memory(i)
    tl = c.memory_timeline
    assert len(tl) < 64
    # decimation halves + doubles the stride; retained samples stay an
    # evenly-spaced subsequence of the offered series
    vals = [v for _, v in tl]
    assert vals == sorted(vals)
    assert c._mem_stride > 1
    # the peak is tracked exactly regardless of which samples survive
    assert c.cache_peak_bytes == n - 1
    c.sample_memory(10 * n)
    assert c.cache_peak_bytes == 10 * n


def test_mem_timeline_unbounded_below_cap():
    c = Counters()
    for i in range(100):
        c.sample_memory(i)
    assert len(c.memory_timeline) == 100
    assert c._mem_stride == 1


def test_reset_restores_timeline_and_obs_state():
    c = Counters()
    c.MEM_TIMELINE_CAP = 16
    for i in range(200):
        c.sample_memory(i)
    assert c._mem_stride > 1
    c.metrics.counter("x").inc(5)
    c.reset()
    assert c.memory_timeline == []
    assert c._mem_stride == 1 and c._mem_seen == 0
    assert c.cache_peak_bytes == 0
    assert c.metrics.counter("x").value == 0.0   # registry reset rides along


def test_bump_many_atomic_and_multi_field():
    """``bump_many`` updates several fields in ONE lock trip: concurrent
    hammering from many threads must lose no update on any field."""
    c = Counters()
    n_threads, n_iters = 8, 3000
    start = threading.Barrier(n_threads)

    def _hammer():
        start.wait()
        for _ in range(n_iters):
            c.bump_many(storage_read_bytes=64, storage_read_paged_bytes=4096,
                        storage_read_ops=1)

    threads = [threading.Thread(target=_hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_iters
    assert c.storage_read_ops == total
    assert c.storage_read_bytes == 64 * total
    assert c.storage_read_paged_bytes == 4096 * total


def test_storage_tier_accounting_exact_under_two_tier_contention():
    """Regression (lint rule R1): StorageTier.write_rows/read_rows mutated
    the shared Counters fields under the TIER's lock, not the Counters'
    own — two tiers sharing one instance (activation + grad files) raced
    and lost updates. The totals must be exact."""
    import tempfile

    import numpy as np

    from repro.core import StorageTier

    c = Counters()
    tiers = [StorageTier(tempfile.mkdtemp(), counters=c) for _ in range(2)]
    for t_ in tiers:
        t_.alloc("f", (64, 8), np.float32)
    arr = np.ones((8, 8), np.float32)
    n_threads, n_iters = 4, 200
    start = threading.Barrier(n_threads)

    def _hammer(i):
        tier = tiers[i % 2]
        start.wait()
        for _ in range(n_iters):
            tier.write_rows("f", 0, arr)
            tier.read_rows("f", 0, 8)

    threads = [
        threading.Thread(target=_hammer, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_iters
    assert c.storage_write_ops == total
    assert c.storage_read_ops == total
    assert c.storage_write_bytes == arr.nbytes * total
    assert c.storage_read_bytes == arr.nbytes * total
    for t_ in tiers:
        t_.close()
