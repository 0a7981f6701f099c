"""Kernel dispatch: route the SSO hot loops to Pallas or the numpy/jnp
reference path by backend, mode, and shape.

The engine and the :class:`~repro.runtime.forward.ForwardRunner` never call
``pl.pallas_call`` directly — they go through a :class:`KernelDispatch`
built from ``PipelineConfig.kernels``:

- ``"auto"`` (default): the reference path on every backend. On CPU,
  interpret-mode Pallas is an emulation (a compiled per-grid-step loop) and
  loses to vectorized numpy on every shape (``benchmarks/kernel_hotpath.py``).
  On a TPU the v5e compiler refuses all three gather/scatter kernels: their
  single-row ``(1, 128)`` blocks (and ``gather_aggregate``'s ``(1, 1)``
  edge-weight block) fail Mosaic lowering with "the last two dimensions of
  your block shape are divisible by 8 and 128", and a scalar-prefetched index
  vector of 262,144 rows overflows the 1 MiB SMEM. So the layers run as the
  plain XLA programs on the chip until the kernels are redesigned with
  8-row-aligned tiles and SMEM-sized index chunks
  (``tests/test_v5e_compile.py`` xfails each refusal strictly).
- ``"reference"``: always the numpy/jnp path (the seed engine's math).
- ``"pallas"``: force the Pallas kernels, with ``interpret=True`` on CPU —
  how CI runs every bit-identity test through the fused path. Bit-identical
  to ``"reference"`` for every schedule and depth. On a TPU the first call
  raises the compiler's refusal above; it never reroutes to ``"reference"``
  and never runs in interpret mode off the CPU.
- ``"pallas-fused"``: additionally route the GCN forward through the
  one-kernel gather+aggregate. Its per-edge accumulate is a fused
  multiply-add — deterministic (pipelined == serial bitwise) and one
  rounding per edge instead of the reference's two, but NOT bit-identical
  to the reference order on rows receiving >= 2 edges (~1 ulp; the
  ``gather_aggregate_ref_fma`` oracle reproduces it exactly). Opt-in for
  exactly that reason.

Dispatch rules beyond the mode knob (documented in ``kernels/README.md``):

- Under plain ``"pallas"``, every model — including GCN — routes through
  the device-side ``gather_rows`` kernel (a bit-exact copy) followed by the
  model's unchanged ``apply_layer`` in its own jit, so the layer program
  compiles to the exact executable the reference path runs: bit-identity
  with the numpy engine holds by construction. The one-kernel aggregate is
  the ``"pallas-fused"`` opt-in above.
- Snapshot-mode training keeps the reference host gather — persisting
  ``GA_p`` requires the gathered copy on the host, which is exactly what the
  fused path eliminates. (The engine picks per call site; see
  ``ForwardRunner.run_layer``.)
- The backward keeps the ``jax.vjp`` boundary at ``GA``: the fused backward
  regathers on device (``gather_rows``) and differentiates the unchanged
  layer function, so no Pallas custom-VJP is needed and gradients stay
  bit-identical to the reference linearization.
- The host-side scatter-add dispatches to the deterministic Pallas
  scatter-grad kernel (device round trip) or the improved numpy reference
  (contiguous slice-add fast path, sorted ``np.add.reduceat`` segments for
  non-contiguous rows, ``np.add.at`` residual).

:meth:`KernelDispatch.scatter_add_rows` returns the path it took
(``"ref"`` or ``"pallas"``); the engine puts it on its ``scatter`` span.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

VALID_MODES = ("auto", "reference", "pallas", "pallas-fused")


def scatter_add_rows_ref(
    buf: np.ndarray, rows: np.ndarray, values: np.ndarray
) -> None:
    """Reference host scatter-add: ``buf[rows] += values`` in row order.

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


class KernelDispatch:
    """Resolves ``PipelineConfig.kernels`` against the jax backend and owns
    the per-kernel call sites (host scatter, fused forward/backward
    builders). One instance per engine; jit caches live on the instance so
    retraces are shared across layers."""

    def __init__(self, mode: str = "auto"):
        if mode not in VALID_MODES:
            raise ValueError(
                f"kernels={mode!r} not in {VALID_MODES}"
            )
        import jax

        backend = jax.default_backend()
        self.requested = mode
        self.backend = backend
        # interpret-mode emulation is the only way to run Pallas on CPU
        self.interpret = backend == "cpu"
        if mode == "auto":
            # see the module docstring: interpret mode is slower than numpy
            # on CPU, and v5e refuses the kernels' row blocks
            mode = "reference"
        self.mode = mode
        self._jit_fwd = {}
        self._jit_bwd = {}
        self._jit_gather = None

    @property
    def use_pallas(self) -> bool:
        return self.mode in ("pallas", "pallas-fused")

    @property
    def fused_aggregate(self) -> bool:
        """One-kernel GCN gather+aggregate (FMA accumulation — see module
        docstring). Deterministic but ~1 ulp off the reference order."""
        return self.mode == "pallas-fused"

    # ------------------------------------------------------- host scatter
    def scatter_add_rows(
        self, buf: np.ndarray, rows: np.ndarray, values: np.ndarray
    ) -> str:
        """In-place ``buf[rows] += values`` — the backward's ∇A write-back;
        returns the path taken, ``"ref"`` or ``"pallas"``.
        Pallas path: deterministic sorted scatter-grad kernel (device round
        trip; unsorted rows are stable-sorted first, so duplicate rows still
        accumulate in their input order). Both paths are bit-identical for
        the engine's sorted-unique row sets."""
        n = rows.size
        if n == 0:
            return "ref"
        r0 = int(rows[0])
        contiguous = int(rows[n - 1]) - r0 + 1 == n and (
            n == 1 or bool(np.all(np.diff(rows) == 1))
        )
        if not self.use_pallas or contiguous:
            # contiguous unique run (the loss layer's arange scatter, dense
            # regather runs): a slice add is bit-identical on every path
            # and beats any kernel launch — shape-based dispatch
            scatter_add_rows_ref(buf, rows, values)
            return "ref"
        import jax.numpy as jnp

        from repro.kernels.gather_scatter import ops

        if rows.size > 1 and not bool(np.all(rows[1:] >= rows[:-1])):
            order = np.argsort(rows, kind="stable")
            rows = rows[order]
            values = values[order]
        out = ops.scatter_add(
            jnp.asarray(buf), jnp.asarray(rows.astype(np.int32)),
            jnp.asarray(values), interpret=self.interpret,
        )
        np.copyto(buf, np.asarray(out))
        return "pallas"

    # ---------------------------------------------- fused layer functions
    def gather_rows_fn(self):
        """Jitted device regather ``(stack, idx) -> stack[idx]`` (a
        bit-exact copy via the Pallas row-DMA gather). Deliberately its own
        jit: the kernel boundary keeps XLA from fusing the gather into the
        consuming layer program, so that program compiles to the exact
        executable the reference path runs on a host-gathered buffer —
        bit-identity with the reference engine holds by construction."""
        if self._jit_gather is None:
            import jax

            from repro.kernels.gather_scatter import ops

            interp = self.interpret
            self._jit_gather = jax.jit(
                lambda stack, idx: ops.gather_rows(
                    stack, idx, interpret=interp
                )
            )
        return self._jit_gather

    def fuses_aggregate(self, spec) -> bool:
        """Whether :meth:`fused_forward_fn` runs ``spec``'s forward as the
        one-kernel gather+aggregate (aggregate-first at every width)
        instead of the model's ``apply_layer``."""
        return spec.name == "gcn" and self.fused_aggregate

    def fused_forward_fn(self, spec, activate: bool):
        """``f(params_l, stack, idx, topo) -> out`` for one forward layer
        over the staged partition stack. Default: regather on device
        (:meth:`gather_rows_fn`, a bit-exact copy) and run the unchanged
        ``apply_layer`` as a separate jit — same executable as the
        reference path, so same bits. ``"pallas-fused"`` + GCN gets the
        truly one-kernel gather+aggregate instead (deterministic FMA
        accumulation, ~1 ulp off the reference order)."""
        key = (spec.name, activate)
        if key not in self._jit_fwd:
            import jax
            import jax.numpy as jnp

            from repro.kernels.gather_scatter import ops

            interp = self.interpret
            if self.fuses_aggregate(spec):
                @jax.jit
                def f(params_l, stack, idx, topo):
                    erows = idx[topo.src]
                    # keep dst sorted across the padding tail: padding
                    # edges (weight 0) are re-pointed at the last row
                    dstk = jnp.where(
                        topo.edge_mask > 0, topo.dst, topo.n_dst - 1
                    ).astype(jnp.int32)
                    agg = ops.gather_aggregate(
                        stack, erows, dstk, topo.edge_weight, topo.n_dst,
                        interpret=interp,
                    )
                    h = agg @ params_l["lin"]["w"] + params_l["lin"]["b"]
                    return jax.nn.relu(h) if activate else h
            else:
                apply = spec.apply_layer
                gather = self.gather_rows_fn()

                @jax.jit
                def apply_jit(params_l, ga, topo):
                    return apply(params_l, ga, topo, activate=activate)

                def f(params_l, stack, idx, topo):
                    return apply_jit(params_l, gather(stack, idx), topo)

            self._jit_fwd[key] = f
        return self._jit_fwd[key]

    def fused_backward_fn(self, spec, activate: bool):
        """``f(params_l, stack, idx, topo, d_out) -> (dp, dga)``: regather
        on device (own jit — see :meth:`gather_rows_fn`), then
        differentiate the unchanged layer function at ``GA``. The vjp jit
        has exactly the reference backward's structure, so it compiles to
        the same executable and ``(dp, dga)`` match the reference bitwise
        (co-jitting the gather would let XLA reassociate the parameter-grad
        reductions — a 1-ulp drift the equivalence tests reject)."""
        key = (spec.name, activate)
        if key not in self._jit_bwd:
            import jax

            apply = spec.apply_layer
            gather = self.gather_rows_fn()

            @jax.jit
            def vjp_jit(params_l, ga, topo, d_out):
                def g(p, a):
                    return apply(p, a, topo, activate=activate)

                _, vjp = jax.vjp(g, params_l, ga)
                dp, dga = vjp(d_out)
                return dp, dga

            def f(params_l, stack, idx, topo, d_out):
                return vjp_jit(params_l, gather(stack, idx), topo, d_out)

            self._jit_bwd[key] = f
        return self._jit_bwd[key]
