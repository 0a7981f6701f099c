"""Observability layer tests (repro/obs/ + its runtime wiring).

Load-bearing properties:

- the tracer records nested/cross-thread spans and exports valid Chrome
  ``trace_event`` JSON (every event schema-complete, async pairs share ids,
  per-thread span ends monotone in record order);
- a DISABLED tracer is free: ``span()`` hands back one shared no-op
  singleton, every recorder early-returns, nothing lands in the ring;
- histogram bucket math: exact count/sum/min/max, single-sample quantiles
  exact, bimodal quantiles within the ±20% consistency budget, p50 <= p99;
- a pipelined training epoch run with ``PipelineConfig(trace=...)`` exports
  a timeline containing >= 1 complete span for EVERY stage that reported
  nonzero ``stage_busy_seconds`` (``Counters.stage`` counts and spans in
  one place); under ``jax.profiler`` every stage span also lands in the
  ``/host:CPU`` plane with integer ``stream``/``seq`` of its unit, and each
  unit's prefetch, gather, H2D and compute wait end in that order;
- each stage is recorded once per unit, and no span times an asynchronous
  dispatch (``compute_fwd``/``compute_bwd``/``kernel:*`` are gone);
- ``EmbeddingServer.stats()`` p50/p99 from the shared histogram agree with
  externally-timed ``np.percentile`` numbers within ±20% (the sliding
  window it replaced);
- live telemetry: Prometheus exposition round-trips (render -> parse) and
  carries the serve-side/slow-lane/trace gauges, the ``LiveSampler`` rings
  are bounded and its never-started path allocates no thread, the polling
  cost is pinned, and ``TelemetryServer`` serves a scrapeable
  ``GET /metrics`` on an ephemeral port;
- the tracer's ring state is observable: ``trace.dropped_events`` /
  ``trace.ring_occupancy`` gauges track a live tracer, and the exported
  timeline self-describes truncation via the ``trace_ring`` metadata event.
"""
import glob
import json
import tempfile
import tracemalloc
import threading
import time
import types

import jax
import numpy as np
import pytest

from repro.core import (
    Counters, HostCache, SSOEngine, StorageIOQueue, StorageTier, build_plan,
)
from repro.graph import (
    gcn_norm_coeffs, kronecker_graph, switching_aware_partition,
)
from repro.graph.csr import add_self_loops
from repro.graph.synthetic import random_features, random_labels
from repro.models.gnn.layers import get_gnn
from repro.obs import (
    EpochSummarizer, Histogram, MetricsRegistry, NULL_SPAN, NULL_TRACER,
    Tracer,
)
from repro.runtime import PipelineConfig

KNOWN_PHASES = {"X", "b", "e", "i", "C", "M"}


def _export(tracer, tmp_path, name="trace.json"):
    path = str(tmp_path / name)
    tracer.export_chrome_trace(path)
    with open(path) as f:
        return json.load(f)


def _spans(tracer, names, seconds=0.0):
    for name in names:
        with tracer.span(name):
            if seconds:
                time.sleep(seconds)


def _assert_event_schema(ev):
    for key in ("name", "ph", "pid", "tid"):
        assert key in ev, f"event missing {key}: {ev}"
    assert ev["ph"] in KNOWN_PHASES
    if ev["ph"] != "M":
        assert "ts" in ev
    if ev["ph"] == "X":
        assert ev["dur"] >= 0.0
    if ev["ph"] in ("b", "e"):
        assert isinstance(ev["id"], str)
    if ev["ph"] == "i":
        assert ev["s"] == "t"


# ----------------------------------------------------------------- span shapes
def test_span_nesting_records_inner_before_outer(tmp_path):
    tr = Tracer()
    with tr.span("outer", layer=1):
        with tr.span("inner"):
            time.sleep(0.001)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert outer["args"] == {"layer": 1}
    doc = _export(tr, tmp_path)
    for ev in doc["traceEvents"]:
        _assert_event_schema(ev)


def test_complete_backdates_span_start():
    # a span is emitted when its block exits, dated back to its start
    tr = Tracer()
    time.sleep(0.002)
    with tr.span("gather", part=3) as sp:
        time.sleep(0.001)
        sp.set(bytes=64)
    (ev,) = tr.events()
    assert ev["ph"] == "X"
    assert ev["dur"] >= 1000.0                  # >= 0.001s, in µs
    assert ev["args"] == {"part": 3, "bytes": 64}
    assert 2000.0 <= ev["ts"]                   # started after the sleep
    assert ev["ts"] + ev["dur"] <= (time.perf_counter() - tr._t0) * 1e6


def test_cross_thread_begin_end_share_id(tmp_path):
    tr = Tracer()
    tr.begin("unit:gather", "1.7", part=2)

    def _finish():
        tr.end("unit:gather", "1.7")

    t = threading.Thread(target=_finish, name="worker-x")
    t.start()
    t.join()
    b, e = tr.events()
    assert (b["ph"], e["ph"]) == ("b", "e")
    assert b["id"] == e["id"] == "1.7"
    assert b["tid"] != e["tid"]
    doc = _export(tr, tmp_path)
    pair = [ev for ev in doc["traceEvents"] if ev["ph"] in ("b", "e")]
    assert len(pair) == 2 and pair[0]["id"] == pair[1]["id"]
    # both threads got a thread_name metadata event
    tnames = {ev["tid"]: ev["args"]["name"] for ev in doc["traceEvents"]
              if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert "worker-x" in tnames.values()
    assert {b["tid"], e["tid"]} <= set(tnames)


def test_per_thread_span_ends_are_monotone(tmp_path):
    tr = Tracer()
    _spans(tr, [f"s{i}" for i in range(20)], seconds=0.0005)
    doc = _export(tr, tmp_path)
    ends = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] != "X":
            continue
        end = ev["ts"] + ev["dur"]
        assert end >= ends.get(ev["tid"], -1.0), (
            "span ends must be monotone per thread in record order"
        )
        ends[ev["tid"]] = end


def test_instant_and_counter_events():
    tr = Tracer()
    tr.instant("cache_evict", part=4, bytes=128)
    tr.counter("cache_bytes", 4096)
    i, c = tr.events()
    assert i["ph"] == "i" and i["args"]["part"] == 4
    assert c["ph"] == "C" and c["args"]["value"] == 4096


def test_ring_bound_drops_oldest_and_counts():
    tr = Tracer(ring_events=8)
    _spans(tr, [f"e{i}" for i in range(20)])
    assert tr.events_recorded == 8
    assert tr.dropped == 12
    assert [e["name"] for e in tr.events()] == [f"e{i}" for i in range(12, 20)]
    tr.clear()
    assert tr.events_recorded == 0 and tr.dropped == 0


def test_export_payload_shape(tmp_path):
    tr = Tracer(ring_events=4)
    _spans(tr, [f"e{i}" for i in range(9)])
    doc = _export(tr, tmp_path)
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["dropped_events"] == 5
    assert all(ev["pid"] == doc["traceEvents"][0]["pid"]
               for ev in doc["traceEvents"])


# --------------------------------------------------------------- disabled path
def test_disabled_tracer_is_inert():
    tr = Tracer(enabled=False)
    s1 = tr.span("a", part=1)
    s2 = tr.span("b")
    assert s1 is s2 is NULL_SPAN  # shared singleton: no per-call allocation
    with s1:
        pass
    s1.set(bytes=1)
    assert tr.bind_unit({"stream": 1, "seq": 0}) is None
    assert tr.current_unit() is None
    tr.begin("y", 1)
    tr.end("y", 1)
    tr.instant("z")
    tr.counter("w", 9)
    assert tr.events_recorded == 0 and tr.dropped == 0


def test_counters_default_tracer_disabled_and_cheap():
    c = Counters()
    assert c.tracer is NULL_TRACER
    with c.stage("gather", part=1):
        pass
    with c.wait("compute_wait_fwd"):
        pass
    assert c.tracer.events_recorded == 0
    assert set(c.stage_busy_seconds) == {"gather"}
    assert set(c.stage_stall_seconds) == {"compute_wait_fwd"}
    # overhead pin: a disabled stage is two clock reads and a locked add;
    # generous bound so loaded CI boxes don't flake (~1us/call typical)
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with c.stage("gather"):
            pass
    assert (time.perf_counter() - t0) / n < 20e-6


def test_record_busy_bridges_to_live_tracer():
    # stage/wait count into today's busy/stall names AND span the block;
    # record_busy/record_stall only add seconds measured elsewhere
    c = Counters()
    c.tracer = Tracer()
    with c.stage("gather", stream=2, seq=5, part=1):
        time.sleep(0.001)
    with c.wait("compute_wait_fwd", stream=2, seq=6):
        time.sleep(0.001)
    c.record_busy("h2d", 0.25)
    c.record_stall("h2d.put", 1e-6)
    names = [e["name"] for e in c.tracer.events()]
    assert names == ["gather", "stall:compute_wait_fwd"]
    g, w = c.tracer.events()
    assert g["args"] == {"stream": 2, "seq": 5, "part": 1}
    assert w["args"] == {"stream": 2, "seq": 6}
    assert c.stage_busy_seconds["gather"] >= 0.001
    assert c.stage_busy_seconds["h2d"] == pytest.approx(0.25)
    assert c.stage_stall_seconds["compute_wait_fwd"] >= 0.001
    assert c.stage_stall_seconds["h2d.put"] == pytest.approx(1e-6)
    snap = c.snapshot()
    assert snap["busy_gather"] == c.stage_busy_seconds["gather"]
    assert snap["stall_compute_wait_fwd"] >= 0.001


def test_disabled_stage_retains_nothing_and_records_nothing():
    c = Counters()
    with c.stage("gather", stream=1, seq=2) as sp:
        assert sp is NULL_SPAN                 # the shared no-op span
    with c.wait("x", stream=1) as sp:
        assert sp is NULL_SPAN
    for i in range(200):                   # warm every code path first
        with c.stage("gather", stream=1, seq=i):
            pass
        with c.wait("compute_wait_fwd", stream=1, seq=i):
            pass
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        for i in range(10_000):
            with c.stage("gather", stream=1, seq=i):
                pass
            with c.wait("compute_wait_fwd", stream=1, seq=i):
                pass
        after = tracemalloc.get_traced_memory()[0]
    finally:
        tracemalloc.stop()
    assert after - before < 1024           # nothing retained per call
    assert NULL_TRACER.events_recorded == 0 and NULL_TRACER.dropped == 0
    assert c.stage_busy_seconds["gather"] > 0.0


def _host_events(prof_dir):
    """``(name, start_ns, end_ns, stats)`` of every event on the profile's
    ``/host:CPU`` plane."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{prof_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events]
    return out


def _profile(prof_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(prof_dir), profiler_options=opts)


def test_stage_span_mirrors_into_profiler(tmp_path):
    c = Counters()
    c.tracer = Tracer()
    _profile(tmp_path)
    try:
        with c.stage("gather", stream=3, seq=7, layer=0, part=1,
                     **{"pass": None}):
            with c.tracer.span("storage_read", file="act0") as sp:
                sp.set(bytes=4096)
        with c.wait("compute_wait_xfer_fwd", stream=3, seq=7):
            pass
    finally:
        jax.profiler.stop_trace()
    evs = {n: st for n, _, _, st in _host_events(tmp_path)}
    # a None argument is left out of the profiler's stats
    assert evs["gather"] == {"stream": 3, "seq": 7, "layer": 0, "part": 1}
    # the nested read inherits the unit; integers stay integers
    assert evs["storage_read"] == {"stream": 3, "seq": 7, "layer": 0,
                                   "file": "act0", "bytes": 4096}
    assert evs["stall:compute_wait_xfer_fwd"] == {"stream": 3, "seq": 7}
    ring = {e["name"]: e["args"] for e in c.tracer.events()}
    assert ring["storage_read"] == {**evs["storage_read"], "pass": None}
    # the unit binding ends with the span that set it
    assert c.tracer.current_unit() is None


def test_io_queue_spans_carry_the_submitters_unit(tmp_path):
    c = Counters()
    c.tracer = Tracer()
    st_ = StorageTier(str(tmp_path), counters=c)
    st_.alloc("a", (8, 4), np.float32)
    q = StorageIOQueue(st_, counters=c)
    try:
        with c.stage("grad_fetch", stream=5, seq=3, layer=1, part=2):
            q.submit_write("a", 0, np.ones((8, 4), np.float32)).result()
            q.submit_read("a", 0, 8).result()
        q.drain()
    finally:
        q.close()
        st_.close()
    evs = c.tracer.events()
    unit = {"stream": 5, "seq": 3, "layer": 1}
    io = [e for e in evs if e["name"] in ("write_behind", "async_read",
                                          "storage_read")]
    assert {e["name"] for e in io} == {"write_behind", "async_read",
                                      "storage_read"}
    for e in io:
        assert {k: e["args"][k] for k in unit} == unit, e
    (rd,) = [e for e in evs if e["name"] == "storage_read"]
    (ar,) = [e for e in evs if e["name"] == "async_read"]
    assert rd["tid"] == ar["tid"] and rd["args"]["bytes"] == 8 * 4 * 4
    assert c.stage_busy_seconds["async_read"] > 0.0


# ------------------------------------------------------------------ histograms
def test_histogram_exact_stats_and_bucket_edges():
    h = Histogram("t", start=1.0, growth=2.0, n_buckets=4)  # bounds 1,2,4,8
    for v in (1.0, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(105.5)
    assert h.mean() == pytest.approx(105.5 / 4)
    # bucket 0: (<=1], bucket 1: (1,2], bucket 2: (2,4], overflow: > 8
    assert h._counts == [1, 1, 1, 0, 1]
    snap = h.snapshot()
    assert snap["min"] == 1.0 and snap["max"] == 100.0


def test_histogram_single_sample_quantiles_exact():
    h = Histogram("t")
    h.observe(0.00321)
    snap = h.snapshot()
    assert snap["p50"] == pytest.approx(0.00321)
    assert snap["p99"] == pytest.approx(0.00321)
    assert snap["mean"] == pytest.approx(0.00321)


def test_histogram_bimodal_quantiles_within_budget():
    h = Histogram("t")
    for _ in range(50):
        h.observe(0.001)
    for _ in range(50):
        h.observe(0.010)
    assert h.percentile(25) == pytest.approx(0.001, rel=0.20)
    assert h.percentile(99) == pytest.approx(0.010, rel=0.20)
    qs = [h.percentile(q) for q in (10, 50, 90, 99)]
    assert qs == sorted(qs)          # quantiles must be monotone in q
    assert h.snapshot()["p50"] <= h.snapshot()["p99"]


def test_histogram_empty_and_reset():
    h = Histogram("t")
    assert h.snapshot() == {"count": 0, "sum": 0.0, "mean": 0.0, "min": 0.0,
                            "max": 0.0, "p50": 0.0, "p99": 0.0}
    h.observe(1.0)
    h.reset()
    assert h.count == 0 and h.snapshot()["p99"] == 0.0


# -------------------------------------------------------------------- registry
def test_registry_get_or_create_snapshot_dump(tmp_path):
    m = MetricsRegistry()
    m.counter("io.ops").inc(3)
    assert m.counter("io.ops") is m.get("io.ops")   # get-or-create
    m.gauge("q.depth", fn=lambda: 7)
    m.histogram("lat").observe(0.5)
    snap = m.snapshot()
    assert snap["io.ops"] == 3.0
    assert snap["q.depth"] == 7
    assert snap["lat"]["count"] == 1
    path = str(tmp_path / "metrics.json")
    m.dump_json(path)
    with open(path) as f:
        assert json.load(f)["q.depth"] == 7
    with pytest.raises(TypeError):
        m.gauge("io.ops")            # kind mismatch must be loud


def test_registry_gauge_callback_rebinds():
    m = MetricsRegistry()
    m.gauge("g", fn=lambda: 1)
    m.gauge("g", fn=lambda: 2)       # last registration wins
    assert m.gauge("g").value == 2
    m.reset()                        # callback gauges survive reset
    assert m.gauge("g").value == 2
    m.gauge("s").set(5.0)
    m.reset()
    assert m.gauge("s").value == 0.0


# ------------------------------------------------------------- epoch summaries
def test_epoch_summarizer_reports_deltas():
    c = Counters()
    s = EpochSummarizer(c)
    c.bump("cache_hits", 90)
    c.bump("cache_misses", 10)
    c.bump("storage_read_bytes", 100)
    c.bump("storage_read_paged_bytes", 162)
    c.record_stall("compute_wait_fwd", 0.5)
    c.record_stall("h2d.put", 0.1)
    line = s.summarize(wall_seconds=2.0)
    assert "epoch=1" in line and "wall=2.00s" in line
    assert "cache_hit=90.0%" in line
    assert "read_amp=1.62x" in line
    assert "stalls[top3]=compute_wait_fwd:0.50,h2d.put:0.10" in line
    # second epoch reports only the delta, not cumulative totals
    c.bump("cache_hits", 10)
    line2 = s.summarize()
    assert "epoch=2" in line2 and "cache_hit=100.0%" in line2
    assert "read_amp=n/a" in line2


# ----------------------------------------------------- pipelined-epoch timeline
def _tiny_workload(n_nodes=600, n_parts=4, d_in=16, seed=0):
    g = add_self_loops(kronecker_graph(n_nodes, 7, seed=seed))
    res = switching_aware_partition(g, n_parts, max_iters=8, seed=seed)
    plan = build_plan(g, res.parts, n_parts, edge_weight=gcn_norm_coeffs(g))
    X = random_features(g.n_nodes, d_in, seed)
    Y = random_labels(g.n_nodes, 8, seed)
    return plan, X[plan.ro.perm], Y[plan.ro.perm]


def test_pipelined_epoch_trace_covers_every_busy_stage(tmp_path):
    plan, Xr, Yr = _tiny_workload()
    dims = [16, 24, 8]
    spec = get_gnn("gcn")
    params = spec.init(jax.random.PRNGKey(0), 16, 24, 8, 2)
    trace = str(tmp_path / "epoch_trace.json")
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    cache = HostCache(64 << 10, st_, c)   # small: force offload traffic
    eng = SSOEngine(spec, plan, dims, st_, cache, c, mode="regather",
                    pipeline=PipelineConfig(depth=2, trace=trace))
    eng.initialize(Xr)
    prof = tmp_path / "profile"
    _profile(prof)
    try:
        eng.run_epoch(params, Yr)
    finally:
        jax.profiler.stop_trace()
    busy = dict(c.stage_busy_seconds)
    eng.close()       # exports the trace
    st_.close()

    # every stage span is in the profiler's host plane, on the device
    # trace's clock, naming its unit by integer stream and seq
    host = _host_events(prof)
    names = {n for n, *_ in host}
    unit_spans = {
        "prefetch", "prefetch_bwd", "storage_read", "gather", "regather",
        "loss_fetch", "grad_fetch", "h2d", "d2h", "write_behind",
        "stall:compute_wait_xfer_fwd", "stall:compute_wait_xfer_loss",
        "stall:compute_wait_xfer_bwd", "scatter", "write_submit",
    }
    assert unit_spans | {"d2h_wait", "drain", "fwd_layer", "loss_layer",
                         "bwd_layer", "epoch"} <= names
    for n, _, _, stats in host:
        if n in unit_spans:
            assert type(stats["stream"]) is int, (n, stats)
            assert type(stats["seq"]) is int, (n, stats)
            assert stats["pass"] in ("fwd", "loss", "bwd"), (n, stats)
    ends = {}
    for n, _, end, stats in host:
        if n in unit_spans:
            ends.setdefault((stats["stream"], stats["seq"]), {})[n] = end
    chains = 0
    for (sid, seq), e in ends.items():
        gather = e.get("gather", e.get("regather"))
        if gather is None:
            continue
        pre = e.get("prefetch", e.get("prefetch_bwd"))
        wait = e.get("stall:compute_wait_xfer_fwd",
                     e.get("stall:compute_wait_xfer_bwd"))
        assert pre <= gather <= e["h2d"] <= wait, (sid, seq, e)
        chains += 1
    assert chains == 4 * plan.n_parts     # 2 forward + 2 regather passes
    scatter_paths = {st["path"] for n, _, _, st in host if n == "scatter"}
    assert scatter_paths == {"ref"}

    with open(trace) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    for ev in evs:
        _assert_event_schema(ev)
    assert busy, "pipelined epoch recorded no stage busy time"
    span_names = {ev["name"] for ev in evs if ev["ph"] == "X"}
    for stage, t in busy.items():
        if t > 0.0:
            assert stage in span_names, (
                f"stage {stage!r} has busy={t}s but no span on the timeline"
            )
    # per-unit lifetime spans: prefetch-start (b) matched by consume-end (e)
    b_ids = {ev["id"] for ev in evs if ev["ph"] == "b"}
    e_ids = {ev["id"] for ev in evs if ev["ph"] == "e"}
    assert b_ids and b_ids == e_ids
    assert any(ev["name"].startswith("unit:") for ev in evs
               if ev["ph"] == "b")
    # structural spans from the engine itself
    assert {"fwd_layer", "bwd_layer", "loss_layer"} <= span_names
    # pipeline worker threads are labeled
    tnames = {ev["args"]["name"] for ev in evs
              if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert any(n.startswith("sso-") for n in tnames)


def _traced_epoch(depth):
    plan, Xr, Yr = _tiny_workload()
    dims = [16, 24, 8]
    spec = get_gnn("gcn")
    params = spec.init(jax.random.PRNGKey(0), 16, 24, 8, 2)
    c = Counters()
    c.tracer = Tracer()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    eng = SSOEngine(spec, plan, dims, st_, HostCache(64 << 10, st_, c), c,
                    mode="regather", pipeline=PipelineConfig(depth=depth))
    eng.initialize(Xr)
    eng.run_epoch(params, Yr)
    eng.close()
    st_.close()
    return plan, c


def test_each_stage_recorded_once_per_unit():
    plan, c = _traced_epoch(depth=2)
    evs = c.tracer.events()
    names = {e["name"] for e in evs}
    # nothing times an asynchronous dispatch
    assert not names & {"compute_fwd", "compute_bwd", "bypass_write"}
    assert not any(n.startswith("kernel:") for n in names)
    seen = {}
    for e in evs:
        if e["ph"] == "X" and e["name"] in (
                "prefetch", "prefetch_bwd", "gather", "regather",
                "loss_fetch", "grad_fetch", "h2d", "scatter"):
            key = (e["name"], e["args"]["stream"], e["args"]["seq"])
            seen[key] = seen.get(key, 0) + 1
    assert seen and set(seen.values()) == {1}
    assert sum(1 for k in seen if k[0] == "regather") == 2 * plan.n_parts
    # the async lifetime pair keeps its id, built from the same integers
    for e in evs:
        if e["ph"] == "b":
            assert e["id"] == f"{e['args']['stream']}.{e['args']['seq']}"


def test_serial_stream_spans_without_busy():
    plan, c = _traced_epoch(depth=0)
    names = {e["name"] for e in c.tracer.events()}
    assert {"gather", "regather", "loss_fetch", "storage_read", "scatter",
            "d2h_wait", "fwd_layer", "bwd_layer"} <= names
    # nothing overlaps a serial stage: no busy seconds are counted
    assert not c.stage_busy_seconds


def test_untraced_run_attaches_no_tracer():
    plan, Xr, Yr = _tiny_workload()
    dims = [16, 24, 8]
    spec = get_gnn("gcn")
    params = spec.init(jax.random.PRNGKey(0), 16, 24, 8, 2)
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    eng = SSOEngine(spec, plan, dims, st_, HostCache(8 << 20, st_, c), c,
                    mode="regather", pipeline=PipelineConfig(depth=1))
    eng.initialize(Xr)
    eng.run_epoch(params, Yr)
    eng.close()
    st_.close()
    assert c.tracer is NULL_TRACER
    assert c.tracer.events_recorded == 0


# ------------------------------------------------- serving latency consistency
class _SlowTier(StorageTier):
    """~0.8ms per ranged read: dominates lookup cost so internal histogram
    percentiles and external wall-clock percentiles measure the same thing."""

    def read_rows(self, name, row0, row1):
        time.sleep(0.0008)
        return super().read_rows(name, row0, row1)

    def read_rows_batched(self, requests):
        time.sleep(0.0008)
        return super().read_rows_batched(requests)


def test_serving_histogram_matches_external_timing():
    from repro.infer import EmbeddingServer

    n, dim = 512, 8
    c = Counters()
    st_ = _SlowTier(tempfile.mkdtemp(), counters=c)
    table = np.random.default_rng(0).standard_normal((n, dim)) \
        .astype(np.float32)
    st_.alloc("emb", (n, dim), np.float32)
    st_.write_rows("emb", 0, table)
    ro = types.SimpleNamespace(perm=np.arange(n), inv_perm=np.arange(n))
    srv = EmbeddingServer(st_, "emb", ro, 256, block_rows=64, counters=c)

    rng = np.random.default_rng(1)
    batches = [rng.integers(0, n, size=32) for _ in range(80)]
    for ids in batches[:10]:
        srv.lookup(ids)
    srv.reset_stats()
    external = []
    for ids in batches[10:]:
        t0 = time.perf_counter()
        srv.lookup(ids)
        external.append(time.perf_counter() - t0)
    s = srv.stats()
    srv.close()
    st_.close()

    # nearest-rank external percentiles: the histogram's cumulative bucket
    # walk is nearest-rank-shaped, while the default linear interpolation
    # lands far below the max when a loaded CI box injects one tail
    # outlier — that's a quantile-definition gap, not an accounting error
    ext_p50 = float(np.percentile(external, 50, method="higher")) * 1e3
    ext_p99 = float(np.percentile(external, 99, method="higher")) * 1e3
    assert s["p50_ms"] == pytest.approx(ext_p50, rel=0.20)
    assert s["p99_ms"] == pytest.approx(ext_p99, rel=0.20)
    assert s["p50_ms"] <= s["p99_ms"]
    assert s["mean_ms"] == pytest.approx(
        float(np.mean(external)) * 1e3, rel=0.20
    )


# ----------------------------------------------------------- live telemetry
def test_prometheus_name_grammar_maps_one_to_one():
    from repro.obs.live import prometheus_name

    assert prometheus_name("storage.io_queue_depth") \
        == "repro_storage_io_queue_depth"
    assert prometheus_name("io.slow_lane") == "repro_io_slow_lane"
    # anything off-grammar is sanitized, never dropped
    assert prometheus_name("weird-name.x") == "repro_weird_name_x"


def test_prometheus_roundtrip_with_serve_and_slowlane_gauges():
    from repro.core.storage import StorageIOQueue
    from repro.infer import EmbeddingServer
    from repro.obs.live import parse_prometheus_text, to_prometheus_text

    n, dim = 128, 8
    c = Counters()
    st_ = StorageTier(tempfile.mkdtemp(), counters=c)
    q = StorageIOQueue(st_, counters=c)
    table = np.random.default_rng(0).standard_normal((n, dim)) \
        .astype(np.float32)
    st_.alloc("emb", (n, dim), np.float32)
    st_.write_rows("emb", 0, table)
    ro = types.SimpleNamespace(perm=np.arange(n), inv_perm=np.arange(n))
    srv = EmbeddingServer(st_, "emb", ro, 64 << 10, block_rows=32,
                          counters=c)
    rng = np.random.default_rng(1)
    for _ in range(5):
        srv.lookup(rng.integers(0, n, size=16))

    snap = c.metrics.snapshot()
    text = to_prometheus_text(snap)
    parsed = parse_prometheus_text(text)
    # the serve-side gauges are scrapeable and carry the live values
    assert parsed["repro_serve_queries"] == 5.0
    assert parsed["repro_serve_rows_served"] == 5 * 16
    assert parsed["repro_serve_hits"] + parsed["repro_serve_misses"] > 0
    assert 0.0 <= parsed["repro_serve_hit_rate"] <= 1.0
    # slow-lane state (not just the flip count) is a live gauge
    assert parsed["repro_io_slow_lane"] == 0.0
    assert "repro_io_slow_lane_flips" in parsed
    assert "repro_storage_io_queue_depth" in parsed
    # histogram -> summary exposition: quantile samples + _sum/_count
    assert parsed['repro_serve_lookup_seconds{quantile="0.5"}'] > 0.0
    assert parsed["repro_serve_lookup_seconds_count"] == 5.0
    # round-trip: every scalar metric survives render -> parse exactly
    for name, v in snap.items():
        if not isinstance(v, dict):
            pname = "repro_" + name.replace(".", "_")
            assert parsed[pname] == pytest.approx(float(v))
    srv.close()
    q.close()
    st_.close()


def test_live_sampler_rings_bounded_and_latest():
    from repro.obs.live import LiveSampler

    c = Counters()
    g = c.metrics.gauge("test.depth")
    s = LiveSampler(c, history=4)
    for i in range(10):
        g.set(float(i))
        s.poll_once()
    assert s.ticks == 10
    ring = s.series("test.depth")
    assert len(ring) == 4                      # bounded: oldest evicted
    assert [v for _, v in ring] == [6.0, 7.0, 8.0, 9.0]
    ts = [t for t, _ in ring]
    assert ts == sorted(ts)
    assert s.latest()["test.depth"] == 9.0
    # histograms land in the rings as their count
    c.metrics.histogram("test.lat").observe(0.5)
    s.poll_once()
    assert s.latest()["test.lat.count"] == 1.0
    assert s.series("never.registered") == []


def test_live_sampler_never_started_allocates_no_thread():
    from repro.obs.live import LiveSampler

    before = threading.active_count()
    s = LiveSampler(Counters())
    assert s.running is False
    assert s._thread is None
    assert threading.active_count() == before
    s.stop()                                   # stop on never-started: no-op
    assert s.running is False


def test_live_sampler_start_stop_lifecycle():
    from repro.obs.live import LiveSampler

    c = Counters()
    before = threading.active_count()
    with LiveSampler(c, interval_s=0.01) as s:
        assert s.running
        assert any(t.name == "obs-live-sampler" for t in threading.enumerate())
        deadline = time.perf_counter() + 5.0
        while s.ticks < 3 and time.perf_counter() < deadline:
            time.sleep(0.005)
        assert s.ticks >= 3
    assert not s.running
    assert threading.active_count() == before
    assert c.threads_leaked == 0
    # restartable after stop
    s.start()
    assert s.running
    s.stop()
    assert not s.running


def test_live_sampler_poll_cost_pinned():
    from repro.obs.live import LiveSampler

    c = Counters()
    for i in range(8):
        c.metrics.gauge(f"pin.g{i}").set(float(i))
    c.metrics.histogram("pin.lat").observe(0.1)
    s = LiveSampler(c, history=64)
    s.poll_once()                              # warm the ring allocation
    n = 200
    t0 = time.perf_counter()
    for _ in range(n):
        s.poll_once()
    per_poll = (time.perf_counter() - t0) / n
    # one registry snapshot + ring appends; generous bound for loaded CI
    # boxes (~30us typical on this registry size)
    assert per_poll < 2e-3, f"poll_once cost {per_poll * 1e6:.0f}us"


def test_sampler_overhead_on_pipelined_epoch_within_noise():
    from repro.obs.live import LiveSampler

    plan, Xr, Yr = _tiny_workload()
    dims = [16, 24, 8]
    spec = get_gnn("gcn")
    params = spec.init(jax.random.PRNGKey(0), 16, 24, 8, 2)

    def epoch_wall(sampler_on):
        c = Counters()
        st_ = StorageTier(tempfile.mkdtemp(), counters=c)
        eng = SSOEngine(spec, plan, dims, st_, HostCache(8 << 20, st_, c), c,
                        mode="regather", pipeline=PipelineConfig(depth=2))
        s = LiveSampler(c, interval_s=0.05) if sampler_on else None
        try:
            eng.initialize(Xr)
            if s:
                s.start()
            t0 = time.perf_counter()
            eng.run_epoch(params, Yr)
            wall = time.perf_counter() - t0
        finally:
            if s:
                s.stop()
            eng.close()
            st_.close()
        if s:
            assert s.ticks >= 1                # it actually sampled the run
        return wall

    epoch_wall(False)                          # warm compile caches
    off = min(epoch_wall(False) for _ in range(2))
    on = min(epoch_wall(True) for _ in range(2))
    # the sampler polls a snapshot 20x/s off the hot path: its cost must
    # vanish into run-to-run noise. Generous bound — loaded CI boxes jitter
    # far more than the sampler itself costs.
    assert on < off * 2.0 + 0.25, (
        f"sampler-on epoch {on:.3f}s vs sampler-off {off:.3f}s"
    )


def test_status_line_reports_load_bearing_state():
    from repro.obs.live import LiveSampler

    c = Counters()
    c.bump("cache_hits", 9)
    c.bump("cache_misses", 1)
    c.bump("storage_read_paged_bytes", 3 << 20)
    line = LiveSampler(c).status_line()
    assert "cache_hit=90.0%" in line
    assert "io_q=" in line and "slow_lane=" in line
    assert "trace_drops=" in line
    assert "read=3.1MB" in line


def test_telemetry_server_scrapeable_on_ephemeral_port():
    import urllib.error
    import urllib.request

    from repro.obs.live import TelemetryServer, parse_prometheus_text

    c = Counters()
    c.metrics.gauge("test.scrape").set(42.0)
    with TelemetryServer(c, port=0) as srv:
        assert srv.port > 0
        url = f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        parsed = parse_prometheus_text(body)
        assert parsed["repro_test_scrape"] == 42.0
        # scrapes see live values, not a cached snapshot
        c.metrics.gauge("test.scrape").set(43.0)
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert parse_prometheus_text(
                resp.read().decode())["repro_test_scrape"] == 43.0
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=10)
    assert c.threads_leaked == 0


# ----------------------------------------------------- tracer ring visibility
def test_trace_ring_gauges_track_live_tracer():
    c = Counters()
    snap = c.metrics.snapshot()
    assert snap["trace.dropped_events"] == 0
    assert snap["trace.ring_occupancy"] == 0.0
    c.tracer = Tracer(ring_events=4)           # gauges follow the rebind
    _spans(c.tracer, [f"e{i}" for i in range(9)])
    snap = c.metrics.snapshot()
    assert snap["trace.dropped_events"] == 5
    assert snap["trace.ring_occupancy"] == 1.0  # ring at capacity


def test_export_trace_ring_metadata_self_describes_truncation(tmp_path):
    tr = Tracer(ring_events=4)
    _spans(tr, [f"e{i}" for i in range(9)])
    doc = _export(tr, tmp_path)
    (meta,) = [ev for ev in doc["traceEvents"]
               if ev["ph"] == "M" and ev["name"] == "trace_ring"]
    assert meta["args"] == dict(dropped_events=5, ring_capacity=4,
                                events_exported=4, truncated=True)
    # an un-truncated export says so
    tr2 = Tracer(ring_events=16)
    _spans(tr2, ["only"])
    doc2 = _export(tr2, tmp_path, "t2.json")
    (meta2,) = [ev for ev in doc2["traceEvents"]
                if ev["ph"] == "M" and ev["name"] == "trace_ring"]
    assert meta2["args"]["truncated"] is False
    assert meta2["args"]["dropped_events"] == 0
    assert meta2["args"]["events_exported"] == 1
