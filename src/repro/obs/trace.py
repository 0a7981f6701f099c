"""Span tracer with Chrome/Perfetto ``trace_event`` export.

The measurement substrate for the pipeline-tuning work (ROADMAP items 3–4):
every runtime stage — prefetch / gather workers, the H2D transfer and D2H
retire threads, the ``StorageIOQueue`` service thread, write-behind, and the
compute loop — records named, thread-attributed spans into one bounded
in-memory ring, and :meth:`Tracer.export_chrome_trace` renders the whole
pipelined epoch as a zoomable timeline in ``ui.perfetto.dev`` (or
``chrome://tracing``).

Recording shapes:

- :meth:`Tracer.span` — a ``with``-scoped span on the current thread
  (Chrome ``"X"`` complete event). An enabled tracer also enters a
  ``jax.profiler.TraceAnnotation`` of the same name and arguments, so the
  span lands in a running profiler session's host plane on the device
  trace's clock (a no-op when no session runs). ``Counters.stage`` /
  ``Counters.wait`` wrap it to also count busy / stall seconds;
- :meth:`Tracer.begin` / :meth:`Tracer.end` — an async span that may START
  on one thread and END on another (Chrome ``"b"``/``"e"`` events keyed by
  an id): the runtime uses these for per-unit lifetimes, prefetch-start →
  compute-consumed, which is what makes the pipeline depth visible.

Plus :meth:`Tracer.instant` (point events, e.g. cache evictions) and
:meth:`Tracer.counter` (counter tracks, e.g. the host-cache byte timeline).

Units: a span whose arguments carry ``stream`` (the layer pass) and ``seq``
(the unit's place in it) makes that unit the thread's current one while it
runs; spans opened inside it without a ``stream`` of their own (a storage
read under a gather) inherit ``stream``/``seq``/``layer``/``pass``.
:meth:`Tracer.bind_unit` sets the current unit directly (the compute loop,
while it consumes a unit; the I/O threads, for a request's submitter).

Hot-path discipline: the ring is a ``deque(maxlen=...)`` — appending drops
the oldest event instead of growing (``dropped`` counts the evictions) — and
the DISABLED tracer does no work at all: ``span()`` returns a shared no-op
singleton (no allocation) and every other recorder early-returns after one
attribute check (pinned by tests). Components reach the tracer through
``Counters.tracer``, which defaults to the module-level :data:`NULL_TRACER`.
This module stays stdlib-only at import time: ``jax.profiler`` is imported
when an enabled tracer is built.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Optional


# the arguments that name a unit of work; nested spans inherit them
UNIT_KEYS = ("stream", "seq", "layer", "pass")


class _NullSpan:
    """Shared no-op context manager returned by a disabled tracer's
    ``span()`` — one module-level instance, so the disabled path allocates
    nothing per call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


NULL_SPAN = _NullSpan()


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation``, or None where jax is missing."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:  # pragma: no cover - jax is a dependency
        return None
    return TraceAnnotation


class _Span:
    """One live span: a ring event when the block exits, and a profiler
    annotation while a session runs."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann", "_prev")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        tracer = self._tracer
        local = tracer._local
        unit = getattr(local, "unit", None)
        args = self._args
        if "stream" in args:
            local.unit = args
        elif unit is not None:
            inherited = {k: unit[k] for k in UNIT_KEYS if k in unit}
            inherited.update(args)
            args = self._args = inherited
        self._prev = unit
        ann = tracer._annotation
        if ann is not None and ann.is_enabled():   # a profiler session runs
            self._ann = ann(self._name, **{k: v for k, v in args.items()
                                            if v is not None})
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Add arguments known only inside the block (bytes read)."""
        self._args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._local.unit = self._prev
        self._tracer._emit("X", self._name, self._t0, dur,
                           self._args or None)
        return False


class Tracer:
    """Thread-safe bounded-ring span recorder.

    Timestamps are ``time.perf_counter`` relative to the tracer's creation
    (same clock as every runtime stall/busy measurement), exported in the
    microseconds Chrome's ``trace_event`` format expects.
    """

    def __init__(self, enabled: bool = True, ring_events: int = 1 << 18):
        self.enabled = bool(enabled)
        self._ring: deque = deque(maxlen=max(1, int(ring_events)))
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._thread_names: dict = {}   # tid -> name at first event
        self.dropped = 0                # events evicted from the full ring
        self._local = threading.local()  # .unit: the thread's current unit
        # every enabled span is mirrored into the JAX profiler
        self._annotation = _profiler_annotation() if self.enabled else None

    # ------------------------------------------------------------- recording
    def _emit(self, ph: str, name: str, t_start: float, dur_s: float = 0.0,
              args: Optional[dict] = None, uid=None) -> None:
        if not self.enabled:
            return
        tid = threading.get_ident()
        if tid not in self._thread_names:
            with self._lock:
                self._thread_names[tid] = threading.current_thread().name
        ring = self._ring
        # the append itself needs no lock (deque appends are atomic); a full
        # ring evicts one event per append. Exact but for appends racing at
        # the moment the ring first fills.
        if len(ring) >= ring.maxlen:
            with self._lock:
                self.dropped += 1
        ring.append((ph, name, t_start, dur_s, tid, args, uid))

    def span(self, name: str, **args):
        """``with tracer.span("gather", stream=4, seq=2, part=3) as sp:`` —
        an ``"X"`` span on the current thread, emitted when the block exits
        and mirrored into the JAX profiler (arguments that are None left
        out); ``sp.set(bytes=n)`` adds an argument from inside the block."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, args)

    def bind_unit(self, unit: Optional[dict]) -> Optional[dict]:
        """Make ``unit`` (``stream``/``seq``/``layer``/``pass``) the
        current thread's unit, which spans without their own ``stream``
        inherit; returns the binding it replaced."""
        if not self.enabled:
            return None
        prev = getattr(self._local, "unit", None)
        self._local.unit = unit
        return prev

    def current_unit(self) -> Optional[dict]:
        """The current thread's unit (see :meth:`bind_unit`), or None."""
        if not self.enabled:
            return None
        return getattr(self._local, "unit", None)

    def begin(self, name: str, uid, **args) -> None:
        """Open an async span keyed by ``(name, uid)``; :meth:`end` may run
        on a DIFFERENT thread (the pipeline's per-unit lifetime spans)."""
        if not self.enabled:
            return
        self._emit("b", name, time.perf_counter(), 0.0, args or None, uid)

    def end(self, name: str, uid) -> None:
        if not self.enabled:
            return
        self._emit("e", name, time.perf_counter(), 0.0, None, uid)

    def instant(self, name: str, **args) -> None:
        """A zero-duration point event (e.g. a cache eviction)."""
        if not self.enabled:
            return
        self._emit("i", name, time.perf_counter(), 0.0, args or None)

    def counter(self, name: str, value) -> None:
        """A sample on a counter track (rendered as a graph in Perfetto,
        e.g. host-cache resident bytes over time)."""
        if not self.enabled:
            return
        self._emit("C", name, time.perf_counter(), 0.0, {"value": value})

    # --------------------------------------------------------------- reading
    @property
    def events_recorded(self) -> int:
        return len(self._ring)

    @property
    def ring_capacity(self) -> int:
        return self._ring.maxlen or 0

    @property
    def ring_occupancy(self) -> float:
        """Fill fraction of the bounded ring (1.0 = at capacity, i.e. the
        next event evicts the oldest) — exported as the
        ``trace.ring_occupancy`` registry gauge."""
        cap = self._ring.maxlen or 0
        return len(self._ring) / cap if cap else 0.0

    def events(self) -> list:
        """Snapshot of the ring as dicts (test/introspection helper; the
        canonical output is :meth:`export_chrome_trace`)."""
        ring = list(self._ring)
        t0 = self._t0
        return [
            dict(ph=ph, name=name, ts=(ts - t0) * 1e6, dur=dur * 1e6,
                 tid=tid, args=args, id=uid)
            for ph, name, ts, dur, tid, args, uid in ring
        ]

    def clear(self) -> None:
        """Drop all recorded events (e.g. after a warmup epoch); thread
        names persist so later events still resolve."""
        with self._lock:
            self._ring.clear()
            self.dropped = 0

    # ---------------------------------------------------------------- export
    def export_chrome_trace(self, path: str) -> str:
        """Write the ring as Chrome ``trace_event`` JSON (the object form:
        ``{"traceEvents": [...]}``) loadable by ``ui.perfetto.dev``.

        Every event carries ``name``/``ph``/``ts``/``pid``/``tid``;
        ``"X"`` events add ``dur``; async ``"b"``/``"e"`` pairs share a
        string ``id``. Thread names are attached via ``"M"`` metadata
        events so the pipeline threads (``sso-prefetch``, ``sso-gather-N``,
        ``sso-h2d``, ``sso-d2h``, ``sso-io``, main) label their tracks.
        """
        pid = os.getpid()
        ring = self.events()
        with self._lock:
            tnames = dict(self._thread_names)
            dropped = self.dropped
        evs = [dict(ph="M", name="process_name", pid=pid, tid=0,
                    args=dict(name="sso-runtime")),
               # self-describing truncation: a reader (or the artifact
               # lint) can tell a short run from a ring that wrapped
               # without consulting anything outside the file
               dict(ph="M", name="trace_ring", pid=pid, tid=0,
                    args=dict(dropped_events=dropped,
                              ring_capacity=self._ring.maxlen or 0,
                              events_exported=len(ring),
                              truncated=dropped > 0))]
        for tid in sorted(tnames):
            evs.append(dict(ph="M", name="thread_name", pid=pid, tid=tid,
                            args=dict(name=tnames[tid])))
        for e in ring:
            ph, tid, uid, args = e["ph"], e["tid"], e["id"], e["args"]
            ev = dict(ph=ph, name=e["name"], cat="sso", pid=pid, tid=tid,
                      ts=round(e["ts"], 3))
            if ph == "X":
                ev["dur"] = round(e["dur"], 3)
            elif ph in ("b", "e"):
                ev["id"] = str(uid)
            elif ph == "i":
                ev["s"] = "t"   # thread-scoped instant
            if args:
                ev["args"] = dict(args)
            evs.append(ev)
        payload = {
            "traceEvents": evs,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": dropped},
        }
        with open(path, "w") as f:
            json.dump(payload, f)
        return path


#: Shared disabled tracer — the default ``Counters.tracer``. All recording
#: methods early-return; ``span()`` hands back the no-op singleton.
NULL_TRACER = Tracer(enabled=False, ring_events=1)
