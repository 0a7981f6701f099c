"""Compile the chip path's device programs for a described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler, which is installed, compiles
for a v5e chip that is described and not attached, and refuses what the
chip would refuse (unaligned blocks, memory over the device's 16 GiB).

- the GCN layer's forward and backward programs (``layer_apply``,
  ``layer_vjp``) at ``chip_smoke.py``'s largest padded work unit, for the
  1024->256 and 256->19 layers of ``gcn-igbm-3l``, fit one chip's HBM;
- so do the GraphSAGE layer's, at the largest padded unit of the
  ``graphsage-reddit`` benchmark plan, for its 602->128 and 128->41 layers
  (both narrow, so both run the transform-first order);
- so do the GAT layer's at the same unit, for the three layers of
  ``gat-reddit``: 602 -> 4x256 concatenated, 1024 -> 4x256 with the skip,
  1024 -> 6x41 averaged. The unit takes the dense path (``gat_dense``), whose
  programs compile to 0.25 GiB of scratch forward and 0.63-0.89 GiB
  backward; the edge path's backward, compiled at the same unit, holds more
  (about 10 GiB).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.core.engine import layer_vjp
from repro.models.gnn.layers import (
    LocalTopo, _attend_edges, _gat, gat_apply, gcn_apply, sage_apply,
)
from repro.runtime.forward import layer_apply

# chip_smoke.py's largest padded unit (r_pad, e_pad, d_pad) at seed 0:
# 262,144 nodes, average degree 12, 16 partitions
SMOKE_UNIT = (262_144, 2_097_152, 32_768)
# the largest padded unit of the benchmark plan of the reddit graph
# (graphsage-reddit, gat-reddit): 16,384 nodes, 6.3M edges, 16 partitions
REDDIT_UNIT = (16_384, 1_048_576, 2_048)
HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without the chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _dense(weights, d_in, d_out):
    """Shapes of a layer of dense weights named ``weights``."""
    return {k: {"w": (d_in, d_out), "b": (d_out,)} for k in weights}


def _unit_args(sharding, unit, shapes, d_in, d_out):
    r, e, d = unit
    S = partial(_sds, sharding)
    topo = LocalTopo(S((e,), jnp.int32), S((e,), jnp.int32), d,
                     S((e,)), S((e,)), S((d,)), S((d,), jnp.int32))
    params = jax.tree.map(S, shapes, is_leaf=lambda x: isinstance(x, tuple))
    return params, S((r, d_in)), topo, S((d, d_out))


def _fits_one_chip(sharding, program, apply, unit, shapes, d_in, d_out,
                   activate):
    params, ga, topo, d_out_arr = _unit_args(sharding, unit, shapes, d_in,
                                             d_out)
    kw = dict(apply=apply, activate=activate)
    if program == "forward":
        lowered = layer_apply.lower(params, ga, topo, **kw)
    else:
        lowered = layer_vjp.lower(params, ga, topo, d_out_arr, **kw)
    m = lowered.compile().memory_analysis()
    used = (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes)
    assert 0 < used < HBM_BYTES
    return m


@pytest.mark.parametrize("d_in,d_out,activate", [(1024, 256, True),
                                                 (256, 19, False)])
@pytest.mark.parametrize("program", ["forward", "backward"])
def test_gcn_layer_program_fits_one_chip(one_chip, program, d_in, d_out,
                                         activate):
    _fits_one_chip(one_chip, program, gcn_apply, SMOKE_UNIT,
                   _dense(("lin",), d_in, d_out), d_in, d_out, activate)


@pytest.mark.parametrize("d_in,d_out,activate", [(602, 128, True),
                                                 (128, 41, False)])
@pytest.mark.parametrize("program", ["forward", "backward"])
def test_sage_layer_program_fits_one_chip(one_chip, program, d_in, d_out,
                                          activate):
    _fits_one_chip(one_chip, program, sage_apply, REDDIT_UNIT,
                   _dense(("self", "nbr"), d_in, d_out), d_in, d_out,
                   activate)


def _gat_shapes(d_in, heads, d_head, d_out):
    """Shapes of a GAT layer of ``heads`` heads of ``d_head``."""
    return {"w": (d_in, heads, d_head), "a_src": (heads, d_head),
            "a_dst": (heads, d_head), "b": (d_out,)}


@pytest.mark.parametrize("d_in,heads,d_head,d_out,activate", [
    (602, 4, 256, 1024, True),     # concatenated heads
    (1024, 4, 256, 1024, True),    # concatenated, with the skip
    (1024, 6, 41, 41, False),      # averaged output heads
])
@pytest.mark.parametrize("program", ["forward", "backward"])
def test_gat_layer_program_fits_one_chip(one_chip, program, d_in, heads,
                                         d_head, d_out, activate):
    _fits_one_chip(one_chip, program, gat_apply, REDDIT_UNIT,
                   _gat_shapes(d_in, heads, d_head, d_out), d_in, d_out, activate)


def test_gat_dense_backward_holds_less_than_edge_backward(one_chip):
    """At the reddit unit the 1024 -> 4x256 layer with the skip runs the
    dense path; the edge path it replaces, compiled at the same unit, still
    fits one chip but holds more scratch."""
    temp = [_fits_one_chip(one_chip, "backward", apply, REDDIT_UNIT,
                           _gat_shapes(1024, 4, 256, 1024), 1024, 1024,
                           True).temp_size_in_bytes
            for apply in (gat_apply, partial(_gat, attend=_attend_edges))]
    assert temp[0] < temp[1]
