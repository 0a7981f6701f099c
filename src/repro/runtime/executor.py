"""Asynchronous pipelined I/O runtime for the SSO engine (paper Fig. 13).

Turns each per-partition work unit into a multi-stage job

    storage-read / prefetch -> host gather -> device transfer -> device compute
         (worker thread)       (worker threads)  (H2D thread)     (main loop)
                                                                      |
                 bypass write-behind (I/O thread) <- D2H retire (retire thread)

flowing through bounded stage queues. The compute stage stays on the caller
thread and consumes gathered buffers strictly in schedule order, so a
pipelined run executes the exact same floating-point program as the serial
one — ``depth=0`` *is* the serial engine, and ``depth>=1`` is bit-identical
to it (asserted by the equivalence tests). What the pipeline changes is only
*when* the I/O happens: partition reads and host gathers for units
``i+1..i+depth`` run while unit ``i`` computes, the next unit's inputs are
staged onto the device (``jax.device_put`` on the transfer thread, bounded
by :class:`DeviceSlotPool` slots) while the current unit's kernel runs, and
bypass writes retire on the storage I/O queue behind the compute — with
``async_d2h`` the device→host result copy itself retires on a dedicated
thread (``copy_to_host_async`` + deferred ``np.asarray``), so the compute
loop never blocks on either direction of the host↔device link.

The gather stage may be sharded across ``gather_workers`` threads; their
out-of-order completions are rejoined by a sequence-numbered
:class:`~repro.runtime.queues.ReassemblyBuffer` before the transfer (or
compute) stage sees them. An optional per-unit aux-fetch (the backward's
∇A^{l+1} read) rides on the gather stage so the entire backward's storage
traffic — loss logits reads, regather/snapshot fetches, grad fetches, and
degraded-mode grad spills — is off the compute thread.

Gather outputs are recycled through a :class:`BufferPool` — with ``depth=1``
this is classic double buffering (one buffer on device feed, one being
assembled), and queue capacity bounds live buffers at ``capacity + 1`` per
shape bucket. The pool's free lists are byte-capped (stalest shape bucket
dropped on overflow) so multi-epoch runs don't pin their peak footprint.
"""
from __future__ import annotations

import logging
import threading
import weakref
from collections import OrderedDict, deque
from typing import Callable, Iterable, List, Optional

import numpy as np

from repro.core.cache import HostCache
from repro.core.counters import Counters
from repro.core.storage import StorageIOQueue, StorageTier
from repro.core.threads import join_bounded, spawn
from repro.runtime.config import PipelineConfig
from repro.runtime.queues import (
    DONE, NO_UNIT, PipelineAbort, ReassemblyBuffer, StageQueue,
)

_log = logging.getLogger("repro.runtime")


def _host_bytes(obj) -> int:
    """Bytes of the host arrays in a stage product (an array, a tuple of
    them, or None)."""
    if isinstance(obj, np.ndarray):
        return int(obj.nbytes)
    if isinstance(obj, tuple):
        return sum(_host_bytes(o) for o in obj)
    return 0


class BufferPool:
    """Reusable host-side gather output buffers, keyed by (shape, dtype).

    The plan's pow2 padding buckets mean a handful of distinct shapes per
    layer, so recycling eliminates nearly all steady-state allocation; the
    pipeline's bounded queues keep at most ``capacity + 1`` buffers of a
    shape in flight.

    Buffers are allocated 64-byte aligned (a uint8 backing allocation with
    an offset view) so ``jax.device_put`` on the XLA CPU backend can alias
    them zero-copy instead of copying — the transfer stage's
    ``zero_copy_h2d`` path depends on this. jax retains the exact ndarray
    object it aliased, which gives the pool a safe deferred-release
    protocol (:meth:`defer_release`): park a weakref callback on the issued
    view and recycle the backing allocation only once the device array (and
    every pending execution reading it) has dropped the view.

    Hygiene guards on top of the plain free-list design:

    - ``max_bytes`` caps the total bytes parked on free lists. On overflow
      the least-recently-used shape bucket is dropped wholesale (``trims``
      counts buckets, and ``pool_trims`` on the shared counters), so a long
      multi-epoch run whose layer shapes drift doesn't pin its all-time peak
      footprint forever.
    - ``release`` refuses buffers that are unsafe to recycle: non-ndarray
      objects (e.g. a device array reaching a host-buffer release path),
      non-contiguous arrays, views of anything but the pool's own aligned
      backing allocations, buffers the pool never issued, and buffers still
      owned by a pending ``StorageIOQueue.submit_write`` (``owner_check``).
      Rejected releases are silently dropped and counted
      (``pool_release_rejects``) — the buffer simply isn't recycled.
    """

    ALIGN = 64

    def __init__(
        self,
        max_bytes: int = 256 << 20,
        counters: Optional[Counters] = None,
        owner_check: Optional[Callable[[np.ndarray], bool]] = None,
    ):
        self._free: "OrderedDict[tuple, list]" = OrderedDict()
        # RLock: deferred-release weakref callbacks can fire on whatever
        # thread happens to drop the last device reference — including one
        # already inside a pool method via a gc pass during allocation.
        self._lock = threading.RLock()
        # buffers currently checked out, id() -> (weakref, raw backing
        # array). Weakrefs (not bare ids) because a buffer dropped without
        # release — e.g. in-flight on an aborted pipeline — is eventually
        # gc'd and its address reused; the identity check against the live
        # referent below keeps such a stale entry from blessing an
        # unrelated array.
        self._issued: dict = {}
        self._issued_sweep_at = 256
        # zero-copied buffers awaiting their device array's death:
        # weakref -> (key, raw). Holding raw here keeps the memory alive
        # for the device alias even after the issued view is dropped.
        self._deferred: dict = {}
        self._free_bytes = 0
        self.max_bytes = int(max_bytes)
        self.counters = counters
        self.owner_check = owner_check
        self.allocations = 0   # fresh aligned allocations (tests/telemetry)
        self.trims = 0         # free-list buckets dropped at the byte cap
        self.rejected = 0      # release() calls refused by the guards
        self.deferred = 0      # defer_release() handoffs (tests/telemetry)
        if counters is not None:
            m = counters.metrics
            m.gauge("pool.free_bytes", fn=lambda: self._free_bytes)
            m.gauge("pool.allocations", fn=lambda: self.allocations)

    @staticmethod
    def _key(shape: tuple, dtype) -> tuple:
        return (tuple(shape), np.dtype(dtype).str)

    @classmethod
    def _alloc_aligned(cls, shape: tuple, dtype) -> tuple:
        """Fresh zeroed buffer as a 64B-aligned view over a uint8 backing
        allocation. Returns ``(view, raw)``; the view keeps ``raw`` alive
        through its base chain."""
        dtype = np.dtype(dtype)
        nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        raw = np.zeros(nbytes + cls.ALIGN, np.uint8)
        off = (-raw.ctypes.data) % cls.ALIGN
        view = raw[off : off + nbytes].view(dtype).reshape(shape)
        return view, raw

    @classmethod
    def _view_of(cls, raw: np.ndarray, key: tuple) -> np.ndarray:
        shape, dts = key
        dtype = np.dtype(dts)
        nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        off = (-raw.ctypes.data) % cls.ALIGN
        return raw[off : off + nbytes].view(dtype).reshape(shape)

    def _mark_issued(self, arr: np.ndarray, raw: np.ndarray) -> None:
        # caller holds self._lock
        self._issued[id(arr)] = (weakref.ref(arr), raw)
        if len(self._issued) > self._issued_sweep_at:
            dead = [k for k, (r, _) in self._issued.items() if r() is None]
            for k in dead:
                del self._issued[k]
            self._issued_sweep_at = max(256, 2 * len(self._issued))

    def acquire(self, shape: tuple, dtype) -> np.ndarray:
        key = self._key(shape, dtype)
        with self._lock:
            lst = self._free.get(key)
            if lst:
                self._free.move_to_end(key)   # bucket is live: keep it young
                arr, raw = lst.pop()
                self._free_bytes -= arr.nbytes
                self._mark_issued(arr, raw)
                return arr
            self.allocations += 1
        arr, raw = self._alloc_aligned(shape, dtype)
        with self._lock:
            self._mark_issued(arr, raw)
        return arr

    def _reject(self) -> None:
        # release() is called from compute/transfer/gather threads at once
        with self._lock:
            self.rejected += 1
        if self.counters is not None:
            self.counters.bump("pool_release_rejects")

    def _park(self, key: tuple, arr: np.ndarray, raw: np.ndarray) -> None:
        # caller holds self._lock
        self._free.setdefault(key, []).append((arr, raw))
        self._free.move_to_end(key)
        self._free_bytes += arr.nbytes
        while self._free_bytes > self.max_bytes and len(self._free) > 1:
            # drop the stalest bucket (not the one just released into)
            _, lst = self._free.popitem(last=False)
            self._free_bytes -= sum(a.nbytes for a, _ in lst)
            self.trims += 1
            if self.counters is not None:
                self.counters.bump("pool_trims")

    def release(self, arr) -> None:
        if not isinstance(arr, np.ndarray) or not arr.flags["C_CONTIGUOUS"]:
            self._reject()
            return
        if self.owner_check is not None and self.owner_check(arr):
            self._reject()
            return
        key = (arr.shape, arr.dtype.str)
        with self._lock:
            ent = self._issued.get(id(arr))
            if ent is None or ent[0]() is not arr:
                # double release, a buffer this pool never issued (incl. any
                # foreign view — pool buffers are views only of their own
                # aligned backing allocations), or a stale id from a buffer
                # that was dropped and gc'd
                accepted = False
            else:
                accepted = True
                del self._issued[id(arr)]
                self._park(key, arr, ent[1])
        if not accepted:
            self._reject()

    def defer_release(self, arr) -> bool:
        """Release a buffer that a zero-copy ``jax.device_put`` is aliasing:
        the backing allocation is parked on the free list only once the
        issued view dies — jax retains the exact ndarray it aliased, so the
        view's death means the device array (and every pending execution
        reading it) is gone. Returns ``False`` (and counts a reject) for
        buffers this pool didn't issue."""
        if not isinstance(arr, np.ndarray):
            self._reject()
            return False
        key = (arr.shape, arr.dtype.str)
        with self._lock:
            ent = self._issued.get(id(arr))
            if ent is None or ent[0]() is not arr:
                ok = False
            else:
                ok = True
                del self._issued[id(arr)]
                # keyed by the ref's id — a weakref to an ndarray is not
                # hashable (hash would delegate to the referent); the entry
                # holds the ref itself alive so the callback can fire
                ref = weakref.ref(arr, self._recycle_raw)
                self._deferred[id(ref)] = (ref, key, ent[1])
                self.deferred += 1
        if not ok:
            self._reject()
        return ok

    def _recycle_raw(self, ref) -> None:
        # weakref callback: the zero-copied view died -> recreate it over
        # the retained backing allocation and park it for reuse
        with self._lock:
            ent = self._deferred.pop(id(ref), None)
            if ent is None:
                return
            _, key, raw = ent
            self._park(key, self._view_of(raw, key), raw)

    @property
    def free_bytes(self) -> int:
        return self._free_bytes

    @property
    def deferred_pending(self) -> int:
        with self._lock:
            return len(self._deferred)

    @property
    def outstanding(self) -> int:
        """Issued buffers still alive and unreleased (dead referents — e.g.
        buffers dropped on an aborted pipeline and since gc'd — don't
        count). The deadlock regression suite asserts this returns to zero
        after a faulted ``run_stream``."""
        with self._lock:
            return sum(1 for r, _ in self._issued.values()
                       if r() is not None)


class DeviceSlotPool:
    """Counted device-side staging slots for the transfer stage.

    A slot is held from the moment the transfer thread begins staging a
    unit's inputs onto the device until the compute loop finishes consuming
    them — so ``n_slots`` bounds the number of units whose inputs are
    device-resident at once. ``n_slots=2`` is the classic double buffer
    (one unit feeding the kernel, one being staged); ``n_slots=1``
    serializes every H2D copy behind the previous unit's compute. Waits are
    abort-aware and charged to the caller's stall name.
    """

    def __init__(self, n_slots: int, counters: Counters,
                 abort: threading.Event):
        self.n = max(1, int(n_slots))
        self.counters = counters
        self.abort = abort
        self._free = list(range(self.n))
        self._cond = threading.Condition()
        self.peak_in_use = 0

    def acquire(self, stall_name: str = "h2d_wait_slot",
                unit: dict = NO_UNIT) -> int:
        with self._cond:
            if not self._free:
                with self.counters.wait(stall_name, **unit):
                    while not self._free:
                        if self.abort.is_set():
                            raise PipelineAbort("device_slots")
                        self._cond.wait(0.02)
            slot = self._free.pop()
            self.peak_in_use = max(self.peak_in_use, self.n - len(self._free))
        return slot

    def release(self, slot: int) -> None:
        with self._cond:
            self._free.append(slot)
            self._cond.notify_all()


class PipelineExecutor:
    """Drives work units through prefetch/gather/transfer worker stages and
    hands the main loop (item, staged-buffer) tuples in schedule order; owns
    the write-behind storage queue for the bypass stage and the D2H retire
    thread for asynchronous result copies."""

    def __init__(
        self,
        cfg: PipelineConfig,
        counters: Counters,
        storage: StorageTier,
        cache: Optional[HostCache] = None,
    ):
        self.cfg = cfg
        self.counters = counters
        self.storage = storage
        self.cache = cache
        self._writer: Optional[StorageIOQueue] = None
        if cfg.enabled and cfg.write_behind:
            self._writer = StorageIOQueue(
                storage,
                max_inflight_bytes=cfg.max_inflight_write_bytes,
                counters=counters,
            )
        self.pool = BufferPool(
            max_bytes=cfg.pool_max_bytes,
            counters=counters,
            owner_check=self._writer_owns,
        )
        # D2H retire thread (lazy): deferred np.asarray + bypass write
        self._retire_cond = threading.Condition()
        self._retire_q: deque = deque()
        self._retire_inflight = 0
        self._retire_exc: Optional[BaseException] = None
        self._retire_thread: Optional[threading.Thread] = None
        self._closed = False
        # distinguishes per-unit async trace span ids across run_stream
        # calls (seq numbers restart at 0 every layer pass)
        self._stream_seq = 0

    def _writer_owns(self, arr: np.ndarray) -> bool:
        w = self._writer
        return w is not None and w.owns(arr)

    # ------------------------------------------------------------ bypass I/O
    @property
    def writer(self) -> Optional[StorageIOQueue]:
        return self._writer

    def write_rows(self, name: str, row0: int, arr: np.ndarray) -> None:
        """Bypass write: write-behind when pipelined, synchronous otherwise.
        Pipelined callers must hand over ownership of ``arr`` (no copy)."""
        if self._writer is not None:
            self._writer.submit_write(name, row0, arr)
        else:
            self.storage.write_rows(name, row0, arr)

    # ------------------------------------------------------------ D2H retire
    def retire_write(self, name: str, row0: int, dev) -> None:
        """Retire a device-resident result to storage: the deferred
        ``np.asarray`` (which completes the ``copy_to_host_async`` the
        caller already started) and the bypass write both run on the retire
        thread, so the compute loop never blocks on the D2H copy. Counted as
        ``d2h`` stage busy + ``d2h_bytes``, spanned for the caller's current
        unit. Falls back to a synchronous copy-and-write when ``async_d2h``
        is off or the pipeline is disabled."""
        if not (self.cfg.enabled and self.cfg.async_d2h):
            arr = np.asarray(dev)
            self.counters.bump("d2h_bytes", arr.nbytes)
            self.write_rows(name, row0, arr)
            return
        # backpressure: each pending retire holds a device result alive, so
        # bound them like staging slots rather than queueing without limit
        cap = max(2, 2 * int(self.cfg.device_slots))
        unit = self.counters.tracer.current_unit()
        with self._retire_cond:
            if self._closed:
                raise RuntimeError("PipelineExecutor is closed")
            if self._retire_exc is not None:
                raise self._retire_exc
            if self._retire_thread is None:
                self._retire_thread = spawn("sso-d2h", self._retire_worker)
            if self._retire_inflight >= cap:
                with self.counters.wait("d2h_submit"):
                    while self._retire_inflight >= cap:
                        self._retire_cond.wait(0.02)
                        if self._retire_exc is not None:
                            raise self._retire_exc
            self._retire_q.append((name, row0, dev, unit))
            self._retire_inflight += 1
            self._retire_cond.notify_all()

    def _retire_worker(self) -> None:
        while True:
            with self._retire_cond:
                while not self._retire_q:
                    if self._closed:
                        return
                    self._retire_cond.wait(0.05)
                name, row0, dev, unit = self._retire_q.popleft()
            try:
                with self.counters.stage("d2h", **(unit or NO_UNIT), file=name,
                                         bytes=int(dev.nbytes)):
                    arr = np.asarray(dev)   # completes the async D2H copy
                    self.counters.bump("d2h_bytes", arr.nbytes)
                    self.write_rows(name, row0, arr)
            except BaseException as e:  # surfaced on the next drain/retire
                with self._retire_cond:
                    self._retire_exc = e
                    self._retire_inflight -= 1
                    self._retire_cond.notify_all()
                continue
            with self._retire_cond:
                self._retire_inflight -= 1
                self._retire_cond.notify_all()

    def _drain_retires(self) -> None:
        with self._retire_cond:
            while self._retire_inflight > 0:
                self._retire_cond.wait(0.05)
            if self._retire_exc is not None:
                exc, self._retire_exc = self._retire_exc, None
                raise exc

    def drain_writes(self) -> None:
        """Barrier: all submitted bypass writes are on storage. Called at
        layer boundaries, before anything reads the freshly written file
        (spanned as ``drain`` on the caller's thread). Retiring D2H copies
        are drained first — they feed the write queue."""
        with self.counters.tracer.span("drain"):
            self._drain_retires()
            if self._writer is not None:
                self._writer.drain()

    # -------------------------------------------------------------- pipeline
    def run_stream(
        self,
        items: Iterable,
        gather_fn: Callable,
        prefetch_fn: Optional[Callable] = None,
        aux_fn: Optional[Callable] = None,
        transfer_fn: Optional[Callable] = None,
        cleanup_fn: Optional[Callable] = None,
        prefetch_stage: str = "prefetch",
        gather_stage: str = "gather",
        aux_stage: str = "aux_fetch",
        wait_stage: str = "compute_wait",
        xfer_wait_stage: str = "compute_wait_xfer",
        xfer_up_stage: str = "xfer_wait_up",
        layer: Optional[int] = None,
        pass_name: Optional[str] = None,
    ):
        """Yield ``(item, buf, aux)`` in input order, where
        ``buf, aux = gather_fn(item), aux_fn(item)`` — or, when
        ``transfer_fn`` is given, ``transfer_fn(item, buf, aux)``'s
        replacement pair (the engine uses this to swap the host buffers for
        pre-staged device arrays; the transfer fn takes ownership of the
        host buffers).

        Serial (``depth=0``): gather, aux, and transfer run inline on the
        caller thread, in that order — exactly the serial engine's sequence.
        Pipelined: a prefetch worker runs ``prefetch_fn`` up to ``depth``
        units ahead (stage-1 storage reads, cache pinning) and
        ``cfg.gather_workers`` workers assemble buffers and run the aux
        fetch (stage-2); out-of-order completions are joined by a
        sequence-numbered :class:`ReassemblyBuffer` so downstream stages
        still consume strictly in input order. With ``cfg.transfer_stage``
        and a ``transfer_fn``, a dedicated transfer thread consumes the
        joined stream and stages each unit's inputs onto the device while
        the previous unit computes, holding a :class:`DeviceSlotPool` slot
        from staging until the compute loop finishes the unit (``2`` slots =
        device-side double buffer). Caller wait time is charged to the
        ``wait_stage`` stall (``xfer_wait_stage`` when the transfer stage is
        on); worker time to ``prefetch_stage`` / ``gather_stage`` /
        ``aux_stage`` / ``h2d`` busy — phase-specific names let
        :meth:`Counters.overlap_summary` split forward from backward
        overlap and report the transfer stage's own overlapped fraction.

        Tracing: every stage and wait is spanned for its unit, named by
        integer ``stream`` (this call) and ``seq`` (the unit's place in
        ``items``) plus ``layer``, ``pass`` (``pass_name``) and ``part``;
        the compute loop's wait names the unit it awaits, and while the
        caller consumes a unit that unit is the compute thread's current
        one, so the caller's own spans carry it too.

        Failure semantics (runtime/README.md): an exception in any worker
        stage sets the shared abort event — every queue/buffer wait is
        abort-aware, so all stages unwind instead of deadlocking — and the
        first error re-raises here after the workers are joined. Workers
        that outlive ``cfg.thread_join_timeout_s`` (wedged in a stuck I/O
        op) are *counted* (``threads_leaked``) and logged, never silently
        dropped. ``cleanup_fn(item, buf, aux)`` is then invoked for every
        in-flight unit stranded in the reassembly buffer, the transfer
        queue, or a worker's hands (gathered/staged but not yet handed to
        the next queue when the abort hit)
        so pooled buffers and pins are returned even on a faulted epoch.
        """
        items = list(items)
        use_xfer = transfer_fn is not None and self.cfg.transfer_stage
        c = self.counters
        tracer = c.tracer
        # stream ids tell the layer passes of one trace apart (seq restarts
        # at 0 every call): the per-unit spans and async pairs key on both
        self._stream_seq += 1
        sid = self._stream_seq

        def _unit(seq, it):
            """The unit's span arguments (none when tracing is off)."""
            if not tracer.enabled:
                return NO_UNIT
            p = getattr(it, "p", None)
            return {"stream": sid, "seq": seq, "layer": layer,
                    "pass": pass_name,
                    "part": int(p) if p is not None else None}

        if not self.cfg.enabled or len(items) <= 1:
            # serial: the stages run inline on the caller thread, spanned
            # but not counted as busy (nothing overlaps them)
            for seq, it in enumerate(items):
                ua = _unit(seq, it)
                with tracer.span(gather_stage, **ua):
                    buf = gather_fn(it)
                if aux_fn is not None:
                    with tracer.span(aux_stage, **ua):
                        aux = aux_fn(it)
                else:
                    aux = None
                if use_xfer:   # same gating as the pipelined path, so the
                    # yielded shape never depends on the item count
                    with tracer.span("h2d", **ua):
                        buf, aux = transfer_fn(it, buf, aux)
                prev = tracer.bind_unit(ua or None)
                try:
                    yield it, buf, aux
                finally:
                    tracer.bind_unit(prev)
            return

        nworkers = max(1, int(self.cfg.gather_workers))
        abort = threading.Event()
        q_ready = StageQueue("prefetch_out", self.cfg.capacity, c, abort)
        reasm = ReassemblyBuffer("gather_out", self.cfg.capacity, c, abort)
        errors: List[BaseException] = []

        def _prefetch_worker():
            try:
                for seq, it in enumerate(items):
                    ua = _unit(seq, it)
                    if tracer.enabled:
                        tracer.begin(f"unit:{gather_stage}", f"{sid}.{seq}",
                                     **ua)
                    if prefetch_fn is not None:
                        with c.stage(prefetch_stage, **ua):
                            prefetch_fn(it)
                    q_ready.put((seq, it), unit=ua)
                for _ in range(nworkers):
                    q_ready.put(DONE)
            except PipelineAbort:
                pass
            except BaseException as e:
                errors.append(e)
                abort.set()

        def _unit_cleanup(unit):
            """Return a stage's in-hand unit (gathered but not handed to
            the next queue when the abort hit) through ``cleanup_fn``."""
            if unit is None or cleanup_fn is None:
                return
            try:
                cleanup_fn(*unit)
            except Exception:
                _log.exception("cleanup_fn failed during unwind")

        def _gather_worker():
            inhand = None
            try:
                while True:
                    x = q_ready.get()
                    if x is DONE:
                        return
                    seq, it = x
                    ua = _unit(seq, it)
                    with c.stage(gather_stage, **ua):
                        buf = gather_fn(it)
                    inhand = (it, buf, None)
                    aux = None
                    if aux_fn is not None:
                        with c.stage(aux_stage, **ua):
                            aux = aux_fn(it)
                        inhand = (it, buf, aux)
                    reasm.put(seq, (it, buf, aux), unit=ua)
                    # ownership handed downstream; drop the stale bindings
                    # too — a retained traceback must not pin a buffer the
                    # pool has since reissued
                    inhand = buf = aux = None
            except PipelineAbort:
                pass
            except BaseException as e:
                errors.append(e)
                abort.set()
            finally:
                _unit_cleanup(inhand)

        threads = [spawn("sso-prefetch", _prefetch_worker, start=False)]
        threads += [
            spawn(f"sso-gather-{i}", _gather_worker, start=False)
            for i in range(nworkers)
        ]

        slots: Optional[DeviceSlotPool] = None
        q_dev: Optional[StageQueue] = None
        if use_xfer:
            slots = DeviceSlotPool(self.cfg.device_slots, c, abort)
            q_dev = StageQueue("xfer_out", slots.n, c, abort)

            def _transfer_worker():
                inhand = None
                try:
                    for seq, it in enumerate(items):
                        ua = _unit(seq, it)
                        it, buf, aux = reasm.get(seq, stall_name=xfer_up_stage,
                                                 unit=ua)
                        inhand = (it, buf, aux)
                        slot = slots.acquire(unit=ua)
                        with c.stage("h2d", **ua) as sp:
                            if tracer.enabled:
                                sp.set(bytes=_host_bytes(buf)
                                       + _host_bytes(aux))
                            buf, aux = transfer_fn(it, buf, aux)
                        # transfer_fn took ownership of the host buffers;
                        # from here the unit is the staged replacement pair
                        inhand = (it, buf, aux)
                        q_dev.put((it, buf, aux, slot), unit=ua)
                        inhand = buf = aux = None  # handed downstream
                except PipelineAbort:
                    pass
                except BaseException as e:
                    errors.append(e)
                    abort.set()
                finally:
                    _unit_cleanup(inhand)

            threads.append(spawn("sso-h2d", _transfer_worker, start=False))

        for t in threads:
            t.start()
        prev_unit = tracer.current_unit()
        try:
            for seq, it in enumerate(items):
                # the compute loop's wait names the unit it awaits
                ua = _unit(seq, it)
                if use_xfer:
                    try:
                        it, buf, aux, slot = q_dev.get(
                            stall_name=xfer_wait_stage, unit=ua, always=True
                        )
                    except PipelineAbort:
                        break
                    tracer.bind_unit(ua or None)
                    yield it, buf, aux
                    # the unit's device inputs are consumed: free its slot so
                    # the transfer thread can stage the next-but-one unit
                    slots.release(slot)
                    buf = aux = None  # consumer owns it; drop stale bindings
                else:
                    try:
                        it, buf, aux = reasm.get(seq, stall_name=wait_stage,
                                                 unit=ua, always=True)
                    except PipelineAbort:
                        break
                    tracer.bind_unit(ua or None)
                    yield it, buf, aux
                    buf = aux = None
                tracer.bind_unit(prev_unit)
                if tracer.enabled:
                    # unit consumed: close its prefetch->compute span
                    tracer.end(f"unit:{gather_stage}", f"{sid}.{seq}")
        finally:
            tracer.bind_unit(prev_unit)
            abort.set()
            join_bounded(threads, self.cfg.thread_join_timeout_s, c,
                         what="pipeline stage thread")
            if cleanup_fn is not None:
                stranded = list(reasm.drain_remaining())
                if q_dev is not None:
                    for x in q_dev.drain_remaining():
                        it, buf, aux, _slot = x
                        stranded.append((it, buf, aux))
                for it, buf, aux in stranded:
                    try:
                        cleanup_fn(it, buf, aux)
                    except Exception:
                        _log.exception("cleanup_fn failed during unwind")
            if errors:
                raise errors[0]

    # ------------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Flush pending retires and writes, then stop the worker threads.
        Shutdown always completes — a pending retire error is re-raised
        only after the threads are joined and the writer is closed."""
        if self._closed:
            return
        self._closed = True
        try:
            self._drain_retires()   # worker keeps servicing until q empties
        finally:
            t = self._retire_thread
            if t is not None:
                with self._retire_cond:
                    self._retire_cond.notify_all()
                join_bounded(t, self.cfg.thread_join_timeout_s,
                             self.counters, what="D2H retire thread")
            if self._writer is not None:
                self._writer.close()
