"""Pipeline runtime configuration (knobs for the async SSO executor)."""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class PipelineConfig:
    """Knobs for the asynchronous cache-(re)gather-bypass pipeline.

    depth
        Lookahead in work units: how many units ahead of the one currently
        computing may be in the prefetch/gather stages. ``0`` disables the
        pipeline entirely — the engine runs the exact serial schedule the
        equivalence tests pin down. ``1`` is classic double buffering.
        Stage queues hold ``max(1, depth)`` units (:attr:`capacity`), and a
        pipelined backward always fetches ∇A^{l+1} on the gather stage.
    write_behind
        Route bypass writes through the storage I/O queue instead of
        blocking the compute loop on them.
    max_inflight_write_bytes
        Write-behind backpressure: ``submit_write`` blocks once this many
        bytes are queued but not yet on storage.
    pin_prefetched
        Pin prefetched partitions in the host cache until their gather
        consumes them, so cache pressure can't evict an in-flight working
        set (pins are counted; over-budget prefetches degrade to bypass).
    gather_workers
        Number of parallel host-gather worker threads. Results are joined
        through a sequence-numbered reassembly buffer, so the compute stage
        still consumes units in strict schedule order (bit-identical math)
        while multi-core boxes shard the gather/aux work.
    transfer_stage
        Run host→device staging on a dedicated transfer thread: the next
        unit's gathered buffer (and aux grad) is ``jax.device_put`` onto the
        device while the current unit's kernel runs, bounded by
        ``device_slots``. The compute loop then consumes pre-staged device
        arrays instead of paying the H2D copy inline.
    device_slots
        Device-side staging slots for the transfer stage. ``2`` is classic
        double buffering (one unit's inputs feeding the kernel, one being
        staged); ``1`` serializes each H2D copy behind the previous unit's
        compute (still correct, no staging ahead).
    async_d2h
        Retire D2H results asynchronously: the compute loop starts
        ``copy_to_host_async`` on the device output and hands it to a retire
        thread that runs the deferred ``np.asarray`` and submits the bypass
        write — the compute loop never blocks on the device→host copy.
    pool_max_bytes
        Cap on bytes parked in the :class:`BufferPool` free lists. On
        overflow the stalest shape bucket is dropped (counted as
        ``pool_trims``) so long multi-epoch runs can't pin peak gather
        footprint forever.
    zero_copy_h2d
        Stage host buffers onto the device with a zero-copy
        ``jax.device_put`` (the :class:`BufferPool`'s 64-byte-aligned
        buffers satisfy the XLA CPU aliasing requirement) instead of the
        defensive ``jnp.array(copy=True)``. Recycling of a zero-copied
        buffer is deferred until the device array holding it is dropped
        (tracked by the pool), so the aliasing hazard the copy used to guard
        against cannot occur. ``False`` restores the forced copy.
    trace
        Path to write a Chrome/Perfetto ``trace_event`` JSON timeline of
        the run (open in ``ui.perfetto.dev``). Enables the span tracer on
        the engine's ``Counters``: every pipeline stage's busy intervals,
        per-unit prefetch→compute lifetimes, stalls ≥ 50 µs, cache
        evictions, and the cache-byte counter track are recorded into a
        bounded in-memory ring and exported on ``engine.close()``. ``None``
        (default) keeps the shared no-op tracer — zero hot-path cost.
    trace_ring_events
        Capacity of the trace ring; once full, the oldest events are
        dropped (the export notes how many under ``otherData``).
    thread_join_timeout_s
        How long teardown waits for each pipeline/retire thread before
        declaring it leaked (logged + counted as ``threads_leaked``) and
        unwinding anyway — the bound on ``run_stream``'s "clean raise,
        never a hang" guarantee when a worker is wedged inside a stuck
        storage op.
    slow_lane_pin
        Degradation response to the I/O queue's EWMA slow-lane flag: while
        the storage lane is flagged slow, prefetched partition blocks are
        forced cache-resident (pinned) even when ``pin_prefetched`` is off,
        so the slow device is not re-read for data the host already holds.
        Counted per forced pin as ``slow_lane_pins``.
    """

    depth: int = 0
    write_behind: bool = True
    max_inflight_write_bytes: int = 64 << 20
    pin_prefetched: bool = True
    gather_workers: int = 1
    transfer_stage: bool = True
    device_slots: int = 2
    async_d2h: bool = True
    pool_max_bytes: int = 256 << 20
    zero_copy_h2d: bool = True
    trace: Optional[str] = None
    trace_ring_events: int = 1 << 18
    thread_join_timeout_s: float = 5.0
    slow_lane_pin: bool = True

    @property
    def enabled(self) -> bool:
        return self.depth > 0

    @property
    def capacity(self) -> int:
        """Bound of each stage queue and of the reassembly buffer: live
        gather buffers stay within ``capacity + gather_workers + 1`` per
        shape bucket."""
        return max(1, self.depth)
