"""Structured Storage Offloading engine (paper §3–§5).

Implements the cache-(re)gather-bypass workflow with two gradient engines:

- ``mode="regather"`` (GriNNder): forward persists only the canonical
  per-layer activation array ``A^l`` (bypass-written to storage); the backward
  *regathers* ``GA_p^{l-1}`` just-in-time from the partition cache and lets
  ``jax.vjp`` recompute the layer intermediates — no snapshots, no α-fold
  amplification.
- ``mode="snapshot"`` (HongTu baseline): forward additionally persists every
  partition's gathered activations ``GA_p^{l-1}``; the backward reads the
  snapshot. Numerically identical, α× more I/O and host footprint.

Both engines drive the same pure layer functions (models/gnn/layers.py), so
gradient equality against whole-graph ``jax.grad`` is exact up to float
reassociation — the paper's "no algorithm change" property (Appendix W).

The forward pass is delegated to the composable
:class:`repro.runtime.forward.ForwardRunner` — the same streamed
gather→transfer→compute→bypass layer pass that powers storage-offloaded
inference (``repro.infer``); training hooks its snapshot persist into the
runner's ``after_compute`` and the backward's regather reuses the runner's
gather/prefetch (same cache keys, same pin protocol).

Execution is delegated to the async pipeline runtime (repro/runtime/): each
layer pass — forward, loss, and backward — streams its work units through
prefetch → gather → device-transfer worker stages while the main thread
computes in schedule order and bypass writes retire on a write-behind I/O
thread. The backward's storage traffic is fully off the compute thread:
loss logits reads and regather/snapshot fetches run on the gather workers,
the ∇A^{l+1} fetch rides the pipeline's aux stage, and degraded-mode grad
spills (plus dirty cache evictions) retire on the storage I/O queue (whose
FIFO orders the later reads behind them). Device transfers are off the
compute thread too: the transfer stage ``jax.device_put``s the next unit's
gathered buffer / labels / aux grad while the current unit's kernel runs
(``PipelineConfig.device_slots`` bounds the staged units), and forward
bypass results retire via ``copy_to_host_async`` + a deferred
``np.asarray`` on the runtime's D2H retire thread.
``pipeline.depth == 0`` is the serial engine; ``depth >= 1`` (with any
``gather_workers``, with or without the transfer stage) overlaps I/O with
compute and is bit-identical to serial (the compute order and every
gathered buffer are unchanged; device copies are exact).
"""
from __future__ import annotations

import time
from functools import partial
from typing import TYPE_CHECKING, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import HostCache
from repro.core.counters import Counters
from repro.core.plan import PartitionPlan, WorkUnit
from repro.core.storage import StorageTier
from repro.models.gnn.layers import GNNSpec, LocalTopo

if TYPE_CHECKING:  # runtime is imported lazily to avoid an import cycle
    from repro.runtime import PipelineConfig


def _act_name(layer: int) -> str:
    return f"act{layer}"


def _grad_name(layer: int) -> str:
    return f"grad{layer}"


def _snap_name(layer: int, p: int) -> str:
    return f"snap{layer}_{p}"


@partial(jax.jit, static_argnames=("apply", "activate"))
def layer_vjp(params_l, ga, topo, d_out, *, apply, activate):
    """One backward layer program: differentiate the layer at ``GA``. Jitted
    once per process and keyed on the layer function, so every engine
    shares its executables."""
    def g(p, a):
        return apply(p, a, topo, activate=activate)

    _, vjp = jax.vjp(g, params_l, ga)
    return vjp(d_out)


@jax.jit
def loss_and_grad(logits, labels, n_total):
    """Masked mean cross-entropy of one unit's logits (``labels < 0`` are
    padding rows) and its gradient; partition sums compose exactly."""
    mask = (labels >= 0).astype(logits.dtype)

    def loss_fn(lg):
        logp = jax.nn.log_softmax(lg, axis=-1)
        ll = jnp.take_along_axis(
            logp,
            jnp.maximum(labels, 0)[:, None].astype(jnp.int32),
            axis=-1,
        )[:, 0]
        return -(ll * mask).sum() / n_total

    return jax.value_and_grad(loss_fn)(logits)


def scatter_add_rows(
    buf: np.ndarray, rows: np.ndarray, values: np.ndarray
) -> None:
    """Host scatter-add ``buf[rows] += values``: the backward's ∇A
    write-back.

    Fast paths, all bit-identical to a bare ``np.add.at`` for the orders
    they accept:

    - contiguous unique row run -> direct slice add (the loss layer's
      ``arange`` scatter and dense regather runs);
    - sorted rows (the engine's ``req_global`` slices are sorted-unique) ->
      segment starts + ``np.add.reduceat``, vectorized instead of
      ``np.add.at``'s per-element inner loop;
    - anything else -> stable-sort first, then the reduceat path.

    Bit-identical to ``add.at`` whenever rows are duplicate-free — which
    every engine call site is. With duplicate rows the segment sum lands on
    the base in ONE rounding instead of per-element (~1 ulp); callers that
    need add.at's exact order for duplicates must not use this.
    """
    n = rows.size
    if n == 0:
        return
    r0 = int(rows[0])
    if int(rows[n - 1]) - r0 + 1 == n and (
        n == 1 or bool(np.all(np.diff(rows) == 1))
    ):
        buf[r0 : r0 + n] += values
        return
    if n > 1 and not bool(np.all(rows[1:] >= rows[:-1])):
        order = np.argsort(rows, kind="stable")
        rows = rows[order]
        values = values[order]
    starts = np.flatnonzero(np.concatenate(([True], rows[1:] > rows[:-1])))
    sums = np.add.reduceat(values, starts, axis=0)
    buf[rows[starts]] += sums


class SSOEngine:
    def __init__(
        self,
        spec: GNNSpec,
        plan: PartitionPlan,
        dims: Sequence[int],              # [d_in, d_h1, ..., d_out]
        storage: StorageTier,
        cache: HostCache,
        counters: Optional[Counters] = None,
        mode: str = "regather",
        overlap: bool = False,
        dtype=np.float32,
        pipeline: Union[PipelineConfig, int, None] = None,
    ):
        # lazy import: repro.runtime depends on repro.core submodules
        from repro.runtime.config import PipelineConfig
        from repro.runtime.executor import PipelineExecutor
        from repro.runtime.forward import ForwardRunner

        assert mode in ("regather", "snapshot")
        self.spec = spec
        self.plan = plan
        self.dims = list(dims)
        self.n_layers = len(dims) - 1
        self.storage = storage
        self.cache = cache
        self.counters = counters or storage.counters
        self.mode = mode
        self.dtype = np.dtype(dtype)
        self._materialized_grads: set = set()
        if pipeline is None:
            # legacy knob: overlap=True was a single-worker next-unit
            # prefetch — depth-1 pipelining subsumes it
            pipeline = PipelineConfig(depth=1 if overlap else 0)
        elif isinstance(pipeline, int):
            pipeline = PipelineConfig(depth=pipeline)
        self.pipeline = pipeline
        self.overlap = pipeline.enabled
        # observability: a trace path swaps the shared no-op tracer on the
        # counters for a live one; every component holding these counters
        # (cache, storage queue, runtime stages) starts recording spans.
        # The timeline is exported on close().
        self._trace_path = pipeline.trace
        if pipeline.trace:
            from repro.obs import Tracer
            self.counters.tracer = Tracer(
                ring_events=pipeline.trace_ring_events
            )
        from repro.obs import EpochSummarizer
        self._summarizer = EpochSummarizer(self.counters)
        self._rt = PipelineExecutor(pipeline, self.counters, storage, cache)
        # device-transfer stage: all three passes consume pre-staged device
        # arrays (H2D on the runtime's transfer thread) instead of paying
        # jnp.asarray on the compute thread
        self._use_xfer = pipeline.enabled and pipeline.transfer_stage
        if self._rt.writer is not None:
            # dirty cache evictions flush through the write-behind queue so
            # an eviction never stalls pipeline workers on a storage write;
            # grad/snap reads below go through the same FIFO for ordering
            cache.set_spill_queue(self._rt.writer)
        # the shared forward layer pass (also the backward's regather path);
        # snapshot-mode backward pins live in the runner's pin table too
        self.fwd_runner = ForwardRunner(
            spec, plan, self.dims, storage, cache, self.counters, self._rt,
            pipeline, dtype=self.dtype,
        )
        self._prefetch_pins = self.fwd_runner.prefetch_pins

    # ------------------------------------------------------------------ jit
    def _bwd(self, activate: bool):
        return partial(layer_vjp, apply=self.spec.apply_layer,
                       activate=activate)

    def compile_programs(self, params: List) -> int:
        """Compile the forward and backward layer programs of every padded
        shape bucket of the plan, a thread per core, before the first epoch;
        returns the number of programs. A cold epoch compiles them one after
        another, and at published widths each takes tens of seconds on v5e."""
        from repro.runtime.forward import compile_parallel, layer_apply

        jobs = []
        for l in range(self.n_layers):
            kw = dict(apply=self.spec.apply_layer,
                      activate=l < self.n_layers - 1)
            for u in self.fwd_runner.shape_buckets():
                ga = jax.ShapeDtypeStruct((u.r_pad, self.dims[l]), self.dtype)
                d_out = jax.ShapeDtypeStruct(
                    (u.d_pad, self.dims[l + 1]), self.dtype)
                jobs.append((layer_apply, (params[l], ga, u.topo), kw))
                jobs.append((layer_vjp, (params[l], ga, u.topo, d_out), kw))
        return compile_parallel(jobs)

    # -------------------------------------------------------------- storage
    def initialize(self, x_reordered: np.ndarray) -> None:
        """Write input features (already permuted by plan.ro.perm) to storage
        partition-wise, alloc per-layer activation files."""
        n = self.plan.n_nodes
        st = self.storage
        for l, d in enumerate(self.dims):
            name = _act_name(l)
            if st.exists(name):
                st.free(name)
            st.alloc(name, (n, d), self.dtype)
        for p in range(self.plan.n_parts):
            u = self.plan.unit(p)
            st.write_rows(_act_name(0), u.v0, x_reordered[u.v0 : u.v1])
        if self.mode == "snapshot":
            for l in range(self.n_layers):
                for p in range(self.plan.n_parts):
                    u = self.plan.unit(p)
                    name = _snap_name(l, p)
                    if st.exists(name):
                        st.free(name)
                    st.alloc(name, (u.n_req, self.dims[l]), self.dtype)

    # --------------------------------------------------------------- gather
    # The gather/prefetch/transfer machinery lives in the shared
    # ForwardRunner; the backward's regather path drives it through these
    # delegates (same cache keys and pin protocol as the forward).
    def _gather(self, layer: int, u: WorkUnit, pad_rows: int) -> np.ndarray:
        return self.fwd_runner.gather(layer, u, pad_rows)

    def _prefetch_unit(self, layer: int, u: WorkUnit) -> None:
        self.fwd_runner.prefetch_unit(layer, u)

    def _h2d(self, arr: np.ndarray):
        return self.fwd_runner.h2d(arr)

    # -------------------------------------------------------------- forward
    def forward(self, params: List) -> None:
        for l in range(self.n_layers):
            after = None
            if self.mode == "snapshot":
                def after(u, ga_host, _l=l):
                    # HongTu: persist GA for the backward pass (α-amplified).
                    # The snapshot is offloaded from the device, so it
                    # transits the device<->host link (paper Table 6:
                    # (2α+1)D forward).
                    self.counters.bump(
                        "d2h_bytes",
                        u.n_req * self.dims[_l] * self.dtype.itemsize,
                    )
                    self._snapshot_put(_l, u.p, ga_host[: u.n_req])
            self.fwd_runner.run_layer(
                l, params[l], activate=(l < self.n_layers - 1),
                after_compute=after,
            )

    # ------------------------------------------------------------ snapshots
    def _snapshot_put(self, layer: int, p: int, ga_real: np.ndarray) -> None:
        name = _snap_name(layer, p)
        # reserve BEFORE the copy (ga_real views a pooled gather buffer that
        # will be recycled): evictions run first and the claim counts toward
        # the budget, so the snapshot copy never overshoots it transiently
        nb = int(ga_real.nbytes)
        reserved = self.cache.reserve(nb)
        snap = np.array(ga_real)
        ok = reserved and self.cache.put(
            ("snap", layer, p), snap, dirty=True, spill_name=name,
            reserved_bytes=nb,
        )
        if not ok:
            # write-behind when pipelined (snap is freshly owned); the
            # forward's layer-boundary drain lands it before any reader
            self._rt.write_rows(name, 0, snap)
            self._materialized_grads.add(("snapdisk", layer, p))

    def _load_snap(self, layer: int, p: int, n_req: int) -> np.ndarray:
        # routed through the I/O queue: a dirty snap eviction spills through
        # the same FIFO, so this read always sees the spilled data
        return self._io_read(_snap_name(layer, p), 0, n_req)

    def _snapshot_prefetch(self, layer: int, u: WorkUnit) -> None:
        """Stage-1 for snapshot-mode backward: warm the unit's snapshot (a
        dirty eviction spilled it to its snap file) before the fetch stage
        needs it, mirroring the regather prefetch."""
        pin = self.pipeline.pin_prefetched
        key = ("snap", layer, u.p)
        resident = self.cache.prefetch(
            key, loader=partial(self._load_snap, layer, u.p, u.n_req), pin=pin,
            size_hint=u.n_req * self.dims[layer] * self.dtype.itemsize,
        )
        if pin and resident:
            self._prefetch_pins[(layer, u.p)] = [key]

    def _snapshot_get(self, layer: int, p: int, u: WorkUnit) -> np.ndarray:
        arr = self.cache.peek(("snap", layer, p))
        if arr is None:
            arr = self._io_read(_snap_name(layer, p), 0, u.n_req)
            self.counters.bump("cache_misses")
        else:
            self.counters.bump("cache_hits")
        buf = self._rt.pool.acquire((u.r_pad, arr.shape[1]), self.dtype)
        buf[: arr.shape[0]] = arr
        buf[arr.shape[0] :] = 0
        for key in self._prefetch_pins.pop((layer, p), ()):
            self.cache.unpin(key)
        return buf

    # ------------------------------------------------------- grad write-back
    def _io_read(self, name: str, a0: int, a1: int) -> np.ndarray:
        """Ranged read routed through the storage I/O queue when pipelined:
        the queue's FIFO orders it behind any in-flight write of the same
        region (degraded-mode grad spills and dirty cache evictions)."""
        w = self._rt.writer
        if w is not None:
            return w.submit_read(name, a0, a1).result()
        return self.storage.read_rows(name, a0, a1)

    def _grad_accumulate(
        self, layer: int, q: int, rows_local: np.ndarray, values: np.ndarray
    ) -> None:
        """Scatter-accumulate ∇A^{layer} rows for source partition q (the
        paper's host write-back buffer with storage spill). The buffer is
        pinned for the duration of the update so a concurrent pipeline-worker
        eviction cannot flush it mid-accumulate."""
        key = ("grad", layer, q)
        a0, a1 = self.plan.ro.partition_slice(q)
        name = _grad_name(layer)
        buf = self.cache.acquire(key)
        if buf is None:
            # reserve before materializing the write-back buffer so the
            # zeros/read never pushes host memory past the cache budget
            nb = (a1 - a0) * self.dims[layer] * self.dtype.itemsize
            reserved = self.cache.reserve(nb)
            try:
                if ("gradmat", layer, q) in self._materialized_grads:
                    buf = self._io_read(name, a0, a1)
                else:
                    buf = np.zeros((a1 - a0, self.dims[layer]), self.dtype)
                    self._materialized_grads.add(("gradmat", layer, q))
            except BaseException:
                if reserved:
                    self.cache.unreserve(nb)
                raise
            ok = reserved and self.cache.put(
                key, buf, dirty=True, pinned=True,
                spill_name=name, spill_row0=a0, reserved_bytes=nb,
            )
            if not ok:
                # degraded mode: read-modify-write on storage. The write
                # retires on the I/O queue (buf is freshly owned and never
                # touched again); later fetches of this region go through
                # the same FIFO, so they see it without blocking here.
                # bump(): accumulates may race pipeline workers' counters
                scatter_add_rows(buf, rows_local, values)
                self._rt.write_rows(name, a0, buf)
                self.counters.bump("host_scatter_bytes", values.nbytes)
                return
        scatter_add_rows(buf, rows_local, values)
        self.cache.release(key)
        self.counters.bump("host_scatter_bytes", values.nbytes)

    def _grad_fetch(self, layer: int, p: int) -> np.ndarray:
        """Read ∇A^{layer} for destination partition p (padded to topo rows).

        Runs on the pipeline's aux stage when pipelined, hiding the
        grad-file read behind the previous unit's compute. The padded output
        comes from the runtime pool — the caller releases it via
        ``self._rt.pool.release`` once the device has consumed it."""
        u = self.plan.unit(p)
        key = ("grad", layer, p)
        a0, a1 = u.v0, u.v1
        buf = self.cache.peek(key)
        if buf is None and ("gradmat", layer, p) in self._materialized_grads:
            buf = self._io_read(_grad_name(layer), a0, a1)
        out = self._rt.pool.acquire((u.d_pad, self.dims[layer]), self.dtype)
        if buf is None:       # never materialized: ∇A rows are zero
            out[:] = 0
        else:
            out[: u.n_dst] = buf
            out[u.n_dst :] = 0
        return out

    # ------------------------------------------------------------- backward
    def backward(self, params: List, labels_reordered: np.ndarray):
        """Returns (loss, grads) where grads is a list of per-layer pytrees."""
        plan, st = self.plan, self.storage
        n = plan.n_nodes
        L = self.n_layers
        rt = self._rt
        # grad files per layer (lazily zero-filled via materialization set)
        for l in range(L + 1):
            name = _grad_name(l)
            if st.exists(name):
                st.free(name)
            st.alloc(name, (n, self.dims[l]), self.dtype)
        self._materialized_grads.clear()

        # ---- loss layer: dL/dA^L per partition. Logits reads are pipelined
        # through run_stream (busy charged to "loss_fetch"); the dlog
        # write-back lands in the grad cache, spilling through the
        # write-behind queue when degraded.
        total_loss = 0.0
        units = [plan.unit(p) for p in plan.schedule]
        use_xfer = self._use_xfer
        tracer = self.counters.tracer

        def loss_fetch(u: WorkUnit) -> np.ndarray:
            logits = st.read_rows(_act_name(L), u.v0, u.v1)
            lg = rt.pool.acquire((u.d_pad, self.dims[L]), self.dtype)
            lg[: u.n_dst] = logits
            lg[u.n_dst :] = 0
            return lg

        def _pad_labels(u: WorkUnit) -> np.ndarray:
            lb = np.full((u.d_pad,), -1, np.int32)
            lb[: u.n_dst] = labels_reordered[u.v0 : u.v1].astype(np.int32)
            return lb

        def loss_transfer(u: WorkUnit, lg: np.ndarray, _aux):
            # stage logits AND padded labels on the transfer thread
            lb = _pad_labels(u)
            lg_dev = self.fwd_runner.stage_h2d(lg)
            lb_dev = jnp.asarray(lb)   # lb is freshly owned: aliasing is fine
            self.counters.bump("h2d_bytes", lb.nbytes)
            return (lg_dev, lb_dev), None

        with tracer.span("loss_layer", units=len(units)):
            for u, lg, _ in rt.run_stream(
                units, loss_fetch,
                transfer_fn=loss_transfer if use_xfer else None,
                cleanup_fn=self.fwd_runner._cleanup_stream,
                gather_stage="loss_fetch", wait_stage="compute_wait_loss",
                xfer_wait_stage="compute_wait_xfer_loss",
                xfer_up_stage="xfer_wait_up_loss",
                layer=L, pass_name="loss",
            ):
                if use_xfer:
                    lg_dev, lb_dev = lg
                    lg_host = None
                else:
                    lg_host = lg
                    lb = _pad_labels(u)
                    # count labels too, matching the transfer-stage path
                    self.counters.bump("h2d_bytes", lg.nbytes + lb.nbytes)
                    lg_dev, lb_dev = jnp.asarray(lg), jnp.asarray(lb)
                loss_p, dlog = loss_and_grad(lg_dev, lb_dev, jnp.float32(n))
                dlog_dst = dlog[: u.n_dst]
                # start the D2H copy; it lands while the loss scalar
                # transfers
                dlog_dst.copy_to_host_async()
                with tracer.span("d2h_wait"):
                    total_loss += float(loss_p)
                    dlog_np = np.asarray(dlog_dst)
                self.counters.bump("d2h_bytes", dlog_np.nbytes)
                if lg_host is not None:
                    rt.pool.release(lg_host)
                with tracer.span("scatter"):
                    self._grad_accumulate(L, u.p, np.arange(u.n_dst), dlog_np)

        # ---- layers L..1
        grads: List = [None] * L
        for l in range(L - 1, -1, -1):
            with tracer.span("bwd_layer", layer=l,
                             units=len(plan.schedule)):
                grads[l] = self._backward_layer(l, params)
            if self.spec.transforms_first(self.dims[l], self.dims[l + 1]):
                self.counters.bump("narrow_aggregate_passes")
            if self.spec.attends():
                self.counters.bump("attention_passes")
                self.counters.bump("attention_units", len(units))
                self.counters.bump(
                    "dense_attention_units",
                    self.spec.dense_attention_units(params[l], units))
        self.cache.drop_layer("grad", 0, flush=False)
        rt.drain_writes()
        st.free(_grad_name(0))
        return total_loss, grads

    def _backward_layer(self, l: int, params: List):
        """One backward layer pass: regather (or snapshot-fetch) GA^l and
        ∇A^{l+1} per unit, differentiate the layer, scatter ∇GA rows into
        ∇A^l; returns the layer's parameter gradient. Ends with the
        consumed ∇A^{l+1} dropped behind a write barrier."""
        plan, st, rt = self.plan, self.storage, self._rt
        L = self.n_layers
        tracer = self.counters.tracer
        bwd = self._bwd(activate=(l < L - 1))
        dW_acc = None
        units = [plan.unit(p) for p in plan.schedule]
        if self.mode == "regather":
            gather_fn = lambda u, _l=l: self._gather(_l, u, u.r_pad)
            prefetch_fn = (
                (lambda u, _l=l: self._prefetch_unit(_l, u))
                if self.pipeline.enabled else None
            )
            gather_stage, prefetch_stage = "regather", "prefetch_bwd"
        else:
            gather_fn = lambda u, _l=l: self._snapshot_get(_l, u.p, u)
            prefetch_fn = (
                (lambda u, _l=l: self._snapshot_prefetch(_l, u))
                if self.pipeline.enabled else None
            )
            gather_stage, prefetch_stage = "snap_fetch", "snap_prefetch"
        # aux stage: fetch ∇A^{l+1} on the gather workers. Safe to run
        # ahead — grad layer l+1 was fully accumulated before this
        # stream started, and this stream only scatters into layer l.
        aux_fn = (
            (lambda u, _l=l: self._grad_fetch(_l + 1, u.p))
            if self.pipeline.enabled else None
        )
        use_xfer = self._use_xfer

        def bwd_transfer(u, ga, d_out):
            # stage GA and ∇A^{l+1} (fetched by the aux stage) on the
            # transfer thread
            do_dev = self.fwd_runner.stage_h2d(d_out)
            return self.fwd_runner.stage_h2d(ga), do_dev

        for u, ga, d_out in rt.run_stream(
            units, gather_fn, prefetch_fn, aux_fn=aux_fn,
            transfer_fn=bwd_transfer if use_xfer else None,
            cleanup_fn=self.fwd_runner._cleanup_stream,
            prefetch_stage=prefetch_stage, gather_stage=gather_stage,
            aux_stage="grad_fetch", wait_stage="compute_wait_bwd",
            xfer_wait_stage="compute_wait_xfer_bwd",
            xfer_up_stage="xfer_wait_up_bwd",
            layer=l, pass_name="bwd",
        ):
            if use_xfer:
                ga_dev, do_dev = ga, d_out
                ga = d_out = None
            else:
                if d_out is None:
                    # serial: the grad fetch runs inline
                    d_out = self._grad_fetch(l + 1, u.p)
                self.counters.bump("h2d_bytes", ga.nbytes + d_out.nbytes)
                ga_dev, do_dev = jnp.asarray(ga), jnp.asarray(d_out)
            dp, dga = bwd(params[l], ga_dev, u.topo, do_dev)
            dga_req = dga[: u.n_req]
            # start the D2H copy; it lands under the dW accumulate
            dga_req.copy_to_host_async()
            dW_acc = dp if dW_acc is None else jax.tree.map(jnp.add, dW_acc, dp)
            with tracer.span("d2h_wait"):
                dga_np = np.asarray(dga_req)
            self.counters.bump("d2h_bytes", dga_np.nbytes)
            if ga is not None:
                rt.pool.release(ga)
            if d_out is not None:
                rt.pool.release(d_out)
            if l > 0:
                # scatter ∇GA rows back to their source partitions
                with tracer.span("scatter"):
                    ptr = u.req_part_ptr
                    for q in u.req_parts:
                        a0, _ = plan.ro.partition_slice(int(q))
                        rows = u.req_global[ptr[q] : ptr[q + 1]] - a0
                        self._grad_accumulate(
                            l, int(q), rows, dga_np[ptr[q] : ptr[q + 1]]
                        )
        with tracer.span("d2h_wait"):
            grads_l = jax.tree.map(np.asarray, dW_acc)
        # drop consumed grad layer l+1 from cache & storage; barrier
        # first so no queued degraded spill targets the freed file
        self.cache.drop_layer("grad", l + 1, flush=False)
        rt.drain_writes()
        st.free(_grad_name(l + 1))
        if self.mode == "snapshot":
            self.cache.drop_layer("snap", l, flush=False)
        return grads_l

    # ----------------------------------------------------------------- step
    def run_epoch(self, params: List, labels_reordered: np.ndarray):
        t0 = time.perf_counter()
        try:
            with self.counters.tracer.span("epoch"):
                self.forward(params)
                loss, grads = self.backward(params, labels_reordered)
        except BaseException:
            # faulted epoch (fatal storage error, stage crash): the stream's
            # own unwind released stranded buffers; drop any pins taken by
            # prefetches whose gather never ran so cache pins return to zero
            # and the engine stays closeable
            self.fwd_runner.release_pins()
            raise
        # one structured line per epoch (repro.obs logger; silent unless
        # logging is configured): stall top-3, cache hit rate, read amp
        self._summarizer.log_epoch(time.perf_counter() - t0)
        return loss, grads

    def close(self) -> None:
        try:
            self._rt.close()
        finally:
            # the runtime's writer is gone: later cache evictions must not
            # submit spills to a closed queue, even if close() raised
            self.cache.set_spill_queue(None)
            tr = self.counters.tracer
            if self._trace_path and tr.enabled:
                tr.export_chrome_trace(self._trace_path)
