"""Compile the chip path's device programs for a described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler, which is installed, compiles
for a v5e chip that is described and not attached, and refuses what the
chip would refuse (unaligned blocks, memory over the device's 16 GiB).

- the GCN layer's forward and backward programs (``layer_apply``,
  ``layer_vjp``) at ``chip_smoke.py``'s largest padded work unit, for the
  1024->256 and 256->19 layers of ``gcn-igbm-3l``, fit one chip's HBM;
- each Pallas gather/scatter kernel is refused at width 1024 (strict xfail,
  so a kernel redesign that compiles must flip it).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.core.engine import layer_vjp
from repro.kernels.gather_scatter.gather_scatter import (
    gather_aggregate_pallas, gather_rows_pallas, scatter_add_pallas,
)
from repro.models.gnn.layers import LocalTopo, gcn_apply
from repro.runtime.forward import layer_apply

# chip_smoke.py's largest padded unit (r_pad, e_pad, d_pad) at seed 0:
# 262,144 nodes, average degree 12, 16 partitions
SMOKE_UNIT = (262_144, 2_097_152, 32_768)
HBM_BYTES = 16 << 30
REFUSAL = ("v5e refuses single-row blocks: 'the last two dimensions of your "
           "block shape are divisible by 8 and 128'")


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


def _unit_args(sharding, d_in, d_out):
    r, e, d = SMOKE_UNIT
    S = partial(_sds, sharding)
    topo = LocalTopo(S((e,), jnp.int32), S((e,), jnp.int32), d,
                     S((e,)), S((e,)), S((d,)), S((d,), jnp.int32))
    params = {"lin": {"w": S((d_in, d_out)), "b": S((d_out,))}}
    return params, S((r, d_in)), topo, S((d, d_out))


@pytest.mark.parametrize("d_in,d_out,activate", [(1024, 256, True),
                                                 (256, 19, False)])
@pytest.mark.parametrize("program", ["forward", "backward"])
def test_gcn_layer_program_fits_one_chip(one_chip, program, d_in, d_out,
                                         activate):
    params, ga, topo, d_out_arr = _unit_args(one_chip, d_in, d_out)
    kw = dict(apply=gcn_apply, activate=activate)
    if program == "forward":
        lowered = layer_apply.lower(params, ga, topo, **kw)
    else:
        lowered = layer_vjp.lower(params, ga, topo, d_out_arr, **kw)
    m = lowered.compile().memory_analysis()
    used = (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes)
    assert 0 < used < HBM_BYTES


def _kernel_case(name, sharding):
    N, D, R, E = 8192, 1024, 4096, 16384
    S = partial(_sds, sharding)
    if name == "gather_rows":
        return gather_rows_pallas, (S((N, D)), S((R,), jnp.int32))
    if name == "gather_aggregate":
        return gather_aggregate_pallas, (
            S((N, D)), S((E,), jnp.int32), S((E,), jnp.int32), S((E,)),
            S((R, D)))
    return scatter_add_pallas, (S((N, D)), S((R,), jnp.int32), S((R, D)))


@pytest.mark.xfail(strict=True, raises=ValueError, reason=REFUSAL)
@pytest.mark.parametrize("kernel", ["gather_rows", "gather_aggregate",
                                    "scatter_add"])
def test_pallas_kernel_compiles_for_v5e(one_chip, kernel):
    fn, args = _kernel_case(kernel, one_chip)
    jax.jit(partial(fn, d_block=128, interpret=False)).lower(*args).compile()
