"""The served AIMC kernels compile for a TPU v5e chip at granite_8b widths.

Nothing runs: the chip is *described* (``get_topology_desc``), not attached,
and the TPU compiler checks each kernel's block shapes, casts and VMEM use —
what interpret mode cannot (Mosaic's (8, 128) tiling rule, its missing
uint32 -> f32 cast). Shapes (B, KB, Np) at tile_rows 512: a decode batch
through the FFN up-projection (K 4096, Np 14336), a prefill batch through
the down-projection (K 14336, Np 4096) and the unembedding (Np 49152).
Read noise is on, so the in-kernel counter PRNG is compiled too.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.ops import (aimc_matmul_pallas_stacked,
                               aimc_matmul_pallas_v2)

M = 512                     # crossbar rows (AimcConfig.tile_rows)
SHAPES = [(8, 8, 14336), (128, 28, 4096), (256, 8, 49152)]


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host. The persistent compile cache stays off
    around these compiles: an entry written without a chip cannot be read
    back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler / topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _operands(sharding, b, kb, np_, lead=()):
    return (_sds((b, kb * M), jnp.float32, sharding),
            _sds(lead + (kb, M, np_), jnp.int8, sharding),
            _sds(lead + (kb, np_), jnp.float32, sharding),
            _sds((1, 1), jnp.float32, sharding),
            _sds((1,), jnp.uint32, sharding),
            _sds(lead + (np_,), jnp.float32, sharding))


@pytest.mark.parametrize("b,kb,np_", SHAPES)
def test_v2_kernel_compiles_for_v5e(one_chip, b, kb, np_):
    bb = min(128, b)
    f = jax.jit(lambda x, w, s_w, s_x, seed, bias: aimc_matmul_pallas_v2(
        x, w, s_w, s_x, seed, bias, adc_step=4.0, sigma=1.5,
        activation="relu", block_b=bb, block_n=512))
    hlo = f.lower(*_operands(one_chip, b, kb, np_)).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("b,kb,np_", SHAPES[:2])
def test_stacked_kernel_compiles_for_v5e(one_chip, b, kb, np_):
    bb = min(128, b)
    f = jax.jit(lambda x, w, s_w, s_x, seed, bias: aimc_matmul_pallas_stacked(
        x, w, s_w, s_x, seed, bias, adc_step=4.0, sigma=1.5,
        activations=("none", "tanh"), block_b=bb, block_n=512))
    hlo = f.lower(*_operands(one_chip, b, kb, np_, lead=(2,))).compile(
    ).as_text()
    assert "tpu_custom_call" in hlo


def test_kernel_compiles_under_float32_execution(one_chip):
    """Float32 execution traces model code at ``highest`` matmul precision
    (`Execution.matmul_precision`); Mosaic refuses an int8 dot at that
    precision, so the kernel pins its own."""
    from repro.models.layers import Execution
    b, kb, np_ = SHAPES[0]

    def f(x, w, s_w, s_x, seed, bias):
        with Execution(compute_dtype="float32").matmul_precision():
            return aimc_matmul_pallas_v2(
                x, w, s_w, s_x, seed, bias, adc_step=4.0, sigma=1.5,
                block_b=b, block_n=512)

    hlo = jax.jit(f).lower(*_operands(one_chip, b, kb, np_)).compile(
    ).as_text()
    assert "tpu_custom_call" in hlo


def test_served_dispatch_maps_kernel_over_four_chip_mesh(topo):
    """GSPMD cannot partition a Mosaic call: under a data:2,model:2 mesh the
    served dispatch (`ops.aimc_matmul_v2`) must shard_map the kernel, each
    chip computing its rows' half of the bit lines with read noise on, and
    gather the halves back into whole rows, split over `data`."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    b, kb, np_ = 8, 8, 14336

    def on(*spec):
        return NamedSharding(mesh, P(*spec))

    args = (_sds((b, kb * M), jnp.float32, on()),
            _sds((kb, M, np_), jnp.int8, on(None, None, "model")),
            _sds((kb, np_), jnp.float32, on(None, "model")),
            _sds((1, 1), jnp.float32, on()),
            _sds((1,), jnp.uint32, on()))
    f = jax.jit(lambda x, w, s_w, s_x, seed: ops.aimc_matmul_v2(
        x, w, s_w, s_x, seed, adc_step=4.0, sigma=1.5, activation="relu",
        impl="pallas_tpu"))
    with jax.set_mesh(mesh):
        compiled = f.lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "all-gather" in hlo
    out = compiled.output_shardings
    assert out.shard_shape((b, np_)) == (b // 2, np_)
