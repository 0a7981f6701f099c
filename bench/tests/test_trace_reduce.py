"""The trace reduction on a trace recorded once on a TPU v5e chip
(``data/probe.xplane.pb``): two annotated ``bench_epoch`` spans, each with
three ``jit_layer_apply`` and one ``jit_loss_and_grad`` execution. The
expected numbers were read off the trace's events by hand."""
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")


@pytest.fixture(scope="module")
def pd():
    return tr.load(DATA)


def test_merge_unions_overlaps():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9), (10, 11)]) == \
        [(0, 4), (5, 9), (10, 11)]


def test_busy_idle_programs_and_gaps(pd):
    d = tr.reduce_profile(pd, label=lambda gap: f"gap@{gap[0]:.0f}")
    assert d["chips"] == 1
    # first op starts at 44,398,838 ns, last ends at 115,905,491 ns
    assert d["window_s"] == pytest.approx(71_506_653e-9, rel=1e-9)
    # union of the 44 op intervals: 35 disjoint runs, 8,587,072 ns
    assert d["busy_s"] == pytest.approx(8_587_072e-9, rel=1e-9)
    assert d["idle_share"] == pytest.approx(1 - 8_587_072 / 71_506_653)
    progs = d["programs"]
    assert set(progs) == {"jit_layer_apply", "jit_loss_and_grad"}
    assert progs["jit_layer_apply"]["count"] == 6
    assert progs["jit_layer_apply"]["seconds"] == pytest.approx(
        (1429986 + 1419087 + 1418981 + 1429946 + 1418975 + 1418917) * 1e-9)
    assert progs["jit_loss_and_grad"]["count"] == 2
    assert progs["jit_loss_and_grad"]["seconds"] == pytest.approx(
        (26358 + 25453) * 1e-9)
    name, s = d["top_ops"][0]
    assert name == "jit_layer_apply/fusion.1"
    assert s == pytest.approx(8_480_981e-9)
    # the longest idle run: from the sixth layer_apply's last op to the
    # second loss_and_grad's first op
    assert d["gaps"][0] == ["gap@93198427", pytest.approx(22_681_881e-9)]
    assert d["gaps"][1] == ["gap@52083927", pytest.approx(22_340_731e-9)]
    assert d["gap_total_s"] == pytest.approx(
        d["window_s"] - d["busy_s"], rel=1e-9)


def test_window_clips_and_annotations(pd):
    spans = tr.host_spans(pd, "bench_epoch")
    assert spans == [(45_235_750, 45_235_750 + 30_844_539),
                     (87_023_979, 87_023_979 + 30_389_210)]
    second = tr.reduce_profile(pd, spans[1])
    assert second["window_s"] == pytest.approx(30_389_210e-9)
    # the second span holds the last three layer_apply executions (the
    # fourth ends 511,370 ns into it) and one loss_and_grad
    assert second["programs"]["jit_layer_apply"]["count"] == 3
    assert second["programs"]["jit_loss_and_grad"]["count"] == 1
    assert 0 < second["busy_s"] < second["window_s"]


def test_no_device_plane_reads_nothing():
    class Empty:
        planes = []

    assert tr.reduce_profile(Empty()) is None
