"""The split of device idle time by pipeline stage (``bench/stage_idle.py``):
on hand-built profiles whose answer is known by construction, and on a
trace recorded on a TPU v5e chip with the program's spans
(``data/stages.xplane.pb``: one epoch of ``igbm3l-train-tight`` cut to
2,048 nodes in 4 parts, ``stage_idle.py --seconds 0.01 --nodes 2048
--parts 4 --keep``). To keep it small, the recording was cut to the TPU
plane's ``XLA Ops`` and ``XLA Modules`` lines and the host plane's program
and ``bench_*`` spans; both reductions read the same numbers on the cut
file as on the whole one (800 KB)."""
import os
from types import SimpleNamespace as NS

import pytest

import stage_idle as si
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "stages.xplane.pb")


def _ev(name, a, b, **stats):
    return NS(name=name, start_ns=float(a), duration_ns=float(b - a),
              stats=list(stats.items()))


def _profile(ops, host):
    dev = NS(name="/device:TPU:0",
             lines=[NS(name="XLA Ops",
                       events=[_ev("%fusion = f32[] fusion()", a, b)
                               for a, b in ops])])
    # the program's spans land on one host line whatever their thread
    cpu = NS(name="/host:CPU", lines=[NS(name="python", events=host)])
    return NS(planes=[cpu, dev])


def test_one_gap_per_label_with_two_units_overlapping():
    """Gap i (100 ns) belongs to label i. In the unit gaps the compute loop
    waits for unit (1, i) while that unit's stage runs, and unit (2, i)
    reads storage all through the same gap: only the awaited unit
    counts."""
    labels = si.LABELS
    ops, host = [], []
    stage_of = {v: k for k, v in si.UNIT_STAGES.items()
                if k in ("storage_read", "regather", "prefetch_bwd", "h2d")}
    for i, label in enumerate(labels):
        a0 = 200 * i
        ops.append((a0, a0 + 100))
        g = (a0 + 100, a0 + 200)
        if label in si.UNIT_ORDER or label == "queued":
            host.append(_ev("stall:compute_wait_xfer_bwd", g[0] - 30,
                            g[1] + 30, stream=1, seq=i, layer=0,
                            **{"pass": "bwd"}))
            if label != "queued":
                host.append(_ev(stage_of[label], g[0] - 50, g[1] + 5,
                                stream=1, seq=i, layer=0, **{"pass": "bwd"}))
            host.append(_ev("storage_read", g[0] - 10, g[1] + 10,
                            stream=2, seq=i, layer=0, **{"pass": "bwd"}))
        elif label != "host":
            host.append(_ev(label, g[0], g[1], stream=1, seq=i,
                            **{"pass": "bwd"}))
    ops.append((200 * len(labels), 200 * len(labels) + 100))
    pd = _profile(ops, host)
    window = (0.0, 200.0 * len(labels) + 100)
    got = si.idle_by_stage(pd, window)
    assert got["idle_s"] == pytest.approx(len(labels) * 100e-9)
    for label in labels:
        assert got["by_stage"][label] == pytest.approx(100e-9), label
    assert got["by_pass"]["none"] == {"host": pytest.approx(100e-9)}
    assert set(got["by_pass"]["bwd"]) == set(labels) - {"host"}
    assert got["labelled_share"] == pytest.approx(1 - 1 / len(labels))


def test_priority_inside_one_wait_and_compute_spans_after_it():
    """One 1,000 ns gap: the loop waits for unit (3, 0) over [0, 600),
    then scatters over [600, 800); the rest is the host's. During the wait
    the unit's prefetch runs over [0, 100), its gather over [100, 400) with
    a storage read nested over [150, 250), its H2D over [450, 550)."""
    u = dict(stream=3, seq=0, layer=1, **{"pass": "fwd"})
    host = [
        _ev("stall:compute_wait_xfer_fwd", 0, 600, **u),
        _ev("prefetch", -50, 100, **u),
        _ev("gather", 100, 400, **u),
        _ev("storage_read", 150, 250, **u),
        _ev("h2d", 450, 550, **u),
        _ev("scatter", 600, 800, path="ref", **u),
        _ev("bench_iter", -100, 1100),        # not the program's
    ]
    pd = _profile([(-100, 0), (1000, 1100)], host)
    got = si.idle_by_stage(pd, (-100.0, 1100.0))
    ns = {k: round(v * 1e9, 6) for k, v in got["by_stage"].items() if v}
    assert ns == {"host_cache": 100, "host_gather": 200, "storage_read": 100,
                  "queued": 100, "h2d": 100, "scatter": 200, "host": 200}
    assert got["idle_s"] == pytest.approx(1000e-9)


def test_no_device_plane_reads_nothing():
    assert si.idle_by_stage(NS(planes=[]), (0.0, 1.0)) is None


@pytest.fixture(scope="module")
def probe():
    return tr.load(DATA)


def test_recorded_probe_labels_cover_the_idle_time(probe):
    (win,) = tr.host_spans(probe, "bench_window")
    dev = tr.reduce_profile(probe, win)
    got = si.idle_by_stage(probe, win)
    idle = dev["window_s"] - dev["busy_s"]
    assert idle > 0
    assert got["idle_s"] == pytest.approx(idle, rel=1e-9)
    assert sum(got["by_stage"].values()) == pytest.approx(idle, rel=0.01)
    spans = si.program_spans(probe)
    unit = [st for n, _, _, st in spans if n in si.UNIT_STAGES]
    assert unit and all(type(st["stream"]) is int and type(st["seq"]) is int
                        for st in unit)
    assert got["by_stage"]["storage_read"] > 0
