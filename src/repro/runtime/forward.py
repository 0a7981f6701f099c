"""Composable pipelined forward pass — the cache→gather→transfer→compute→
bypass chain shared by training and inference.

:class:`ForwardRunner` owns the forward half of the SSO workflow that used to
live inside ``SSOEngine.forward``: partition-block loading through the
:class:`~repro.core.cache.HostCache`, the host-side gather (one sequential
run per source partition), the pipeline prefetch stage (vectored storage
reads + counted cache pins), H2D staging on the runtime's transfer thread,
the jitted layer apply, and the bypass write of the output activations —
all streamed through :meth:`PipelineExecutor.run_stream` in strict schedule
order, so a pipelined layer pass stays bit-identical to the serial one.

Two drivers share it:

- ``SSOEngine`` (training): runs every layer through :meth:`run_layer` and
  hooks ``after_compute`` in snapshot mode to persist ``GA_p^{l-1}``; the
  backward's regather reuses :meth:`gather`/:meth:`prefetch_unit`
  (same cache keys, same pin protocol).
- ``OffloadedInference`` (serving): forward-only, so it adds the
  inference-only wins on top — per-layer storage truncation (layer ``l-1``'s
  activation file is freed as soon as layer ``l`` finishes) and optional
  fp16 on-storage activations (``store_dtype``; gathers upcast to the
  compute dtype, bypass writes downcast).

``store_dtype`` controls what lives on storage (and therefore in the host
cache, whose entries are raw storage blocks); compute always happens in
``dtype``. With ``store_dtype == dtype`` the gather uses the GIL-releasing
``np.take`` fast path and the byte flow is exactly the training engine's.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import HostCache
from repro.core.counters import Counters
from repro.core.plan import PartitionPlan, WorkUnit
from repro.core.storage import StorageTier
from repro.runtime.config import PipelineConfig


def act_file(layer: int) -> str:
    """Canonical per-layer activation file name (shared with the engine)."""
    return f"act{layer}"


@partial(jax.jit, static_argnames=("apply", "activate"))
def layer_apply(params_l, ga, topo, *, apply, activate):
    """One forward layer program. Jitted once per process and keyed on the
    layer function, so every engine and runner shares its executables."""
    return apply(params_l, ga, topo, activate=activate)


def compile_parallel(jobs) -> int:
    """Compile ``(jitted_fn, args, static_kwargs)`` jobs on a thread per
    core; returns the number of jobs. XLA compiles each program on one core
    and releases the GIL, so the per-shape compiles of a cold run overlap
    instead of queueing through the warm-up epoch; a later call with
    arguments of the same shapes finds the executable in memory. The
    caller's ``jax.default_matmul_precision`` (a thread-local setting that
    keys the executables) is carried into the workers."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    precision = jax.config.jax_default_matmul_precision

    def compile_one(job):
        fn, args, kw = job
        with jax.default_matmul_precision(precision):
            fn.lower(*args, **kw).compile()

    jobs = list(jobs)
    workers = max(1, min(len(jobs), os.cpu_count() or 1))
    with ThreadPoolExecutor(workers, thread_name_prefix="sso-compile") as ex:
        for f in [ex.submit(compile_one, j) for j in jobs]:
            f.result()
    return len(jobs)


class ForwardRunner:
    def __init__(
        self,
        spec,
        plan: PartitionPlan,
        dims,
        storage: StorageTier,
        cache: HostCache,
        counters: Counters,
        rt,                       # PipelineExecutor (owned by the driver)
        pipeline: PipelineConfig,
        dtype=np.float32,
        store_dtype=None,
        act_kind: str = "act",
        act_name: Callable[[int], str] = act_file,
    ):
        self.spec = spec
        self.plan = plan
        self.dims = list(dims)
        self.storage = storage
        self.cache = cache
        self.counters = counters
        self._rt = rt
        self.pipeline = pipeline
        self.dtype = np.dtype(dtype)
        self.store_dtype = (
            np.dtype(store_dtype) if store_dtype is not None else self.dtype
        )
        self.act_kind = act_kind
        self.act_name = act_name
        self._use_xfer = pipeline.enabled and pipeline.transfer_stage
        # (layer, p) -> keys the prefetch stage actually pinned for that
        # unit; the gather stage pops and releases exactly these (prefetch
        # of a unit strictly precedes its gather via the stage queues)
        self.prefetch_pins: Dict = {}

    # ------------------------------------------------------------------ jit
    def fwd_fn(self, activate: bool):
        return partial(layer_apply, apply=self.spec.apply_layer,
                       activate=activate)

    def shape_buckets(self):
        """One unit per padded ``(r_pad, e_pad, d_pad)`` bucket of the plan:
        the units that compile a distinct layer program."""
        reps = {}
        for u in self.plan.units:
            reps.setdefault((u.r_pad, u.e_pad, u.d_pad), u)
        return list(reps.values())

    # --------------------------------------------------------------- gather
    def load_part_block(self, layer: int, q: int) -> np.ndarray:
        a0, a1 = self.plan.ro.partition_slice(q)
        return self.storage.read_rows(self.act_name(layer), a0, a1)

    def block_nbytes(self, layer: int, q: int) -> int:
        """On-storage (= in-cache) size of partition q's block of layer
        ``layer`` — what the prefetch stage reserves before loading."""
        a0, a1 = self.plan.ro.partition_slice(q)
        return (a1 - a0) * self.dims[layer] * self.store_dtype.itemsize

    def gather(self, layer: int, u: WorkUnit, pad_rows: int) -> np.ndarray:
        """Assemble GA_p^{layer} from the partition cache (paper's host-side
        gather: one sequential run per source partition). The output buffer
        comes from the runtime pool — the caller returns it via
        ``rt.pool.release`` once the device has consumed it."""
        d = self.dims[layer]
        buf = self._rt.pool.acquire((pad_rows, d), self.dtype)
        buf[u.n_req :] = 0  # rows [0, n_req) are fully overwritten below
        ptr = u.req_part_ptr
        for q in u.req_parts:
            block = self.cache.get(
                (self.act_kind, layer, int(q)),
                loader=partial(self.load_part_block, layer, int(q)),
                size_hint=self.block_nbytes(layer, int(q)),
            )
            a0, _ = self.plan.ro.partition_slice(int(q))
            rows = u.req_global[ptr[q] : ptr[q + 1]] - a0
            if block.dtype == buf.dtype:
                # np.take releases the GIL for numeric dtypes (unlike
                # advanced indexing), letting worker-thread gathers overlap
                # jit dispatch; mode="clip" skips the bounds-check path
                # (rows are plan-valid)
                np.take(block, rows, axis=0, out=buf[ptr[q] : ptr[q + 1]],
                        mode="clip")
            else:
                # reduced-precision storage: upcast into the compute buffer
                buf[ptr[q] : ptr[q + 1]] = block[rows]
        # release exactly the pins the prefetch stage took for THIS unit
        # (none in serial mode or when a prefetch couldn't keep residency)
        for key in self.prefetch_pins.pop((layer, u.p), ()):
            self.cache.unpin(key)
        # bump(): gathers may run on several pipeline workers concurrently
        self.counters.bump(
            "host_gather_bytes", u.n_req * d * self.dtype.itemsize
        )
        return buf

    def prefetch_unit(self, layer: int, u: WorkUnit) -> None:
        """Stage-1: make (and keep) the unit's source partitions resident.
        Every missing partition is fetched in ONE vectored storage
        submission; block sizes are passed so the cache reserves room BEFORE
        the blocks are materialized (host memory never transiently exceeds
        the budget)."""
        pin = self.pipeline.pin_prefetched
        if not pin and self.pipeline.slow_lane_pin:
            # degradation: while the storage lane is flagged slow (EWMA
            # latency spike on the I/O queue), force this unit's blocks
            # cache-resident so the slow device isn't re-read for data the
            # host already holds
            w = getattr(self._rt, "writer", None)
            if w is not None and w.slow_lane:
                pin = True
                self.counters.bump("slow_lane_pins")
        keys = [(self.act_kind, layer, int(q)) for q in u.req_parts]
        name = self.act_name(layer)
        sizes = {k: self.block_nbytes(layer, k[2]) for k in keys}

        def batch_loader(missing):
            reqs = []
            for (_, _, q) in missing:
                a0, a1 = self.plan.ro.partition_slice(q)
                reqs.append((name, a0, a1))
            return self.storage.read_rows_batched(reqs)

        res = self.cache.prefetch_many(keys, batch_loader, pin=pin, sizes=sizes)
        pinned = [k for k in keys if res.get(k)] if pin else []
        if pinned:
            self.prefetch_pins[(layer, u.p)] = pinned

    # ------------------------------------------------------- fault unwinding
    def release_pins(self) -> None:
        """Unwind path: unpin every prefetched block whose gather never ran
        (aborted pipeline). Idempotent; called after the stage threads are
        joined, so no gather is concurrently popping entries."""
        while self.prefetch_pins:
            try:
                _, keys = self.prefetch_pins.popitem()
            except KeyError:  # pragma: no cover - raced with a live gather
                break
            for key in keys:
                self.cache.unpin(key)

    def release_gather(self, obj) -> None:
        """Unwind path: hand any stranded gather product back to the buffer
        pool. Handles every shape the stream stages carry — pooled ndarrays
        and post-transfer tuples (device arrays are skipped; the pool's
        release guards make an over-eager call on a non-pool object a counted
        no-op).
        """
        if obj is None:
            return
        if isinstance(obj, tuple):
            for o in obj:
                self.release_gather(o)
            return
        if isinstance(obj, np.ndarray):
            self._rt.pool.release(obj)

    def _cleanup_stream(self, _u, buf, aux) -> None:
        """``run_stream`` cleanup_fn: release the pooled buffers of a unit
        stranded in flight when the pipeline unwound."""
        self.release_gather(buf)
        self.release_gather(aux)

    # ----------------------------------------------------- transfer staging
    @staticmethod
    def h2d(arr: np.ndarray):
        """Stage a host array onto the device with a GUARANTEED copy.
        ``jax.device_put`` zero-copies 64-byte-aligned host buffers on the
        CPU backend, which would let a staged device array alias a recycled
        pool buffer; ``jnp.array(copy=True)`` always materializes an
        independent device buffer (and on an accelerator is the same H2D
        DMA either way). Blocks until the copy lands so the caller may
        recycle ``arr`` immediately."""
        dev = jnp.array(arr, copy=True)
        dev.block_until_ready()
        return dev

    def stage_h2d(self, arr: np.ndarray, defer: bool = True):
        """Stage a pooled host buffer onto the device and hand it back to
        the pool.

        With ``pipeline.zero_copy_h2d`` (and ``defer``), the staging is a
        zero-copy ``jax.device_put`` — the pool's buffers are 64-byte
        aligned, so the XLA CPU backend aliases them instead of copying —
        and the buffer is returned via :meth:`BufferPool.defer_release`:
        recycling waits until the device array (and every pending execution
        reading it) has died, which closes the aliasing hazard the forced
        ``jnp.array(copy=True)`` used to guard against. If ``device_put``
        copied anyway (non-CPU backend), jax drops the host view right away
        and the deferred release fires immediately — the protocol is
        agnostic to whether aliasing happened.

        ``defer=False`` (snapshot mode's keep-host staging) always copies
        and leaves the buffer's ownership with the caller."""
        if defer and self.pipeline.zero_copy_h2d:
            dev = jax.device_put(arr)
            dev.block_until_ready()
            self.counters.bump("h2d_bytes", arr.nbytes)
            self._rt.pool.defer_release(arr)
            return dev
        dev = self.h2d(arr)
        self.counters.bump("h2d_bytes", arr.nbytes)
        if defer:
            self._rt.pool.release(arr)
        return dev

    def _make_transfer_fn(self, keep_host: bool):
        def transfer(u: WorkUnit, ga: np.ndarray, _aux):
            """H2D staging for one forward unit (runs on the transfer
            thread): stage the gathered buffer onto the device while the
            previous unit's kernel runs, then hand the host buffer back to
            the pool — unless the driver's ``after_compute`` hook still
            needs it on the compute loop (snapshot mode)."""
            if keep_host:
                dev = self.stage_h2d(ga, defer=False)
                return (dev, ga), None
            return (self.stage_h2d(ga), None), None

        return transfer

    # -------------------------------------------------------------- forward
    def run_layer(
        self,
        l: int,
        params_l,
        activate: bool,
        after_compute: Optional[Callable[[WorkUnit, np.ndarray], None]] = None,
        out_name: Optional[str] = None,
    ) -> None:
        """Stream one forward layer pass: gather GA^l for every scheduled
        unit, apply the layer, and bypass-write the output activations to
        ``out_name`` (default ``act{l+1}``).

        ``after_compute(u, ga_host)`` runs on the compute loop with the
        unit's host gather buffer still alive (the transfer stage is told to
        keep it) — the training engine's snapshot persist hook. The runner
        releases the buffer afterwards.

        Ends with a write barrier and an invalidation of cached blocks of
        the output layer (they would be stale for any later reader).
        """
        rt = self._rt
        use_xfer = self._use_xfer
        keep_host = after_compute is not None
        name_out = out_name if out_name is not None else self.act_name(l + 1)
        cast = self.store_dtype != self.dtype
        fwd = self.fwd_fn(activate)
        gather_fn = lambda u, _l=l: self.gather(_l, u, u.r_pad)
        transfer_fn = self._make_transfer_fn(keep_host)
        units = [self.plan.unit(p) for p in self.plan.schedule]
        prefetch_fn = (
            (lambda u, _l=l: self.prefetch_unit(_l, u))
            if self.pipeline.enabled else None
        )
        with self.counters.tracer.span("fwd_layer", layer=l,
                                       units=len(units)):
            try:
                self._run_layer_stream(
                    l, params_l, fwd, activate, after_compute, name_out, cast,
                    units, gather_fn, prefetch_fn, transfer_fn, use_xfer,
                    keep_host,
                )
            except BaseException:
                # faulted epoch: pins taken by prefetches whose gather never
                # ran must not outlive the stream (HostCache pins return to
                # zero — the deadlock regression suite's contract)
                self.release_pins()
                raise
            if self.spec.transforms_first(self.dims[l], self.dims[l + 1]):
                self.counters.bump("narrow_aggregate_passes")
            if self.spec.attends():
                self.counters.bump("attention_passes")
                self.counters.bump("attention_units", len(units))
                self.counters.bump(
                    "dense_attention_units",
                    self.spec.dense_attention_units(params_l, units))
            # barrier: the next layer reads name_out — all writes must be
            # down (drain_writes retires pending D2H copies first)
            rt.drain_writes()
            # the output layer was just rewritten: cached blocks of it
            # (loaded by a previous epoch's gathers) are stale — drop before
            # any reader
            self.cache.drop_layer(self.act_kind, l + 1, flush=False)

    def _run_layer_stream(
        self, l, params_l, fwd, activate, after_compute, name_out, cast,
        units, gather_fn, prefetch_fn, transfer_fn, use_xfer, keep_host,
    ) -> None:
        rt = self._rt
        tracer = self.counters.tracer
        for u, ga, _ in rt.run_stream(
            units, gather_fn, prefetch_fn,
            transfer_fn=transfer_fn if use_xfer else None,
            cleanup_fn=self._cleanup_stream,
            wait_stage="compute_wait_fwd",
            xfer_wait_stage="compute_wait_xfer_fwd",
            xfer_up_stage="xfer_wait_up_fwd",
            layer=l, pass_name="fwd",
        ):
            if use_xfer:
                ga_dev, ga_host = ga
                out = fwd(params_l, ga_dev, u.topo)
            else:
                ga_host = ga
                ga_dev = jnp.asarray(ga)
                self.counters.bump("h2d_bytes", ga.nbytes)
                out = fwd(params_l, ga_dev, u.topo)
            out_dst = out[: u.n_dst]
            if use_xfer and self.pipeline.async_d2h and not cast:
                # start the D2H copy now; the retire thread runs the
                # deferred np.asarray + bypass write
                out_dst.copy_to_host_async()
                out_np = None
            else:
                with tracer.span("d2h_wait"):
                    out_np = np.asarray(out_dst)
                self.counters.bump("d2h_bytes", out_np.nbytes)
                if cast:
                    # reduced-precision storage: downcast before the
                    # bypass write (out_np is freshly owned)
                    out_np = out_np.astype(self.store_dtype)
            if after_compute is not None:
                after_compute(u, ga_host)
            if ga_host is not None and (not use_xfer or keep_host):
                # the transfer thread recycled the host buffer already
                # unless it was told to keep it for after_compute
                rt.pool.release(ga_host)
            with tracer.span("write_submit"):
                # bypass: output activations go straight to storage
                # (write-behind when pipelined; out_np is freshly owned)
                if out_np is None:
                    rt.retire_write(name_out, u.v0, out_dst)
                else:
                    rt.write_rows(name_out, u.v0, out_np)
