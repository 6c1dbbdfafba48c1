"""The served AIMC kernels compile for a TPU v5e chip at granite_8b widths.

Nothing runs: the chip is *described* (``get_topology_desc``), not attached,
and the TPU compiler checks each kernel's block shapes, casts and VMEM use —
what interpret mode cannot (Mosaic's (8, 128) tiling rule, its missing
uint32 -> f32 cast). Shapes (B, KB, Np) at tile_rows 512: a decode batch
through the FFN up-projection (K 4096, Np 14336), a prefill batch through
the down-projection (K 14336, Np 4096) and the unembedding (Np 49152).
Read noise is on, so the in-kernel counter PRNG is compiled too.

The grouped expert kernel compiles at mellum2_12b's widths (8 layers x 64
experts, d_model 2304, expert width 896) for a decode step's 256 routed
rows in blocks of 8 and a prefill's 12288 in blocks of 128, and the whole
served decode closure of that configuration reads the stacked expert codes
in place: its compiled loop holds the kernel and no slice of the stacks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.ops import (aimc_expert_pallas,
                               aimc_matmul_pallas_stacked,
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


# (rows, block rows, K blocks, Np, gates): decode gate+up and down, prefill
EXPERT_SHAPES = [(704, 8, 5, 896, 2), (704, 8, 2, 2304, 1),
                 (20480, 128, 5, 896, 2), (20480, 128, 2, 2304, 1)]


@pytest.mark.parametrize("rows,bm,kb,np_,gates", EXPERT_SHAPES)
def test_expert_kernel_compiles_for_v5e(one_chip, rows, bm, kb, np_, gates):
    n_layers, n_experts = 8, 64
    f = jax.jit(lambda x, ws, ss, sx, layer, blocks, used: aimc_expert_pallas(
        x, ws, ss, sx, layer, blocks, used, adc_step=4.0, block_rows=bm,
        block_n=np_))
    args = (_sds((rows, kb * M), jnp.float32, one_chip),
            tuple(_sds((n_layers, n_experts, kb, M, np_), jnp.int8, one_chip)
                  for _ in range(gates)),
            tuple(_sds((n_layers, n_experts, kb, np_), jnp.float32, one_chip)
                  for _ in range(gates)),
            _sds((n_experts,), jnp.float32, one_chip),
            _sds((1,), jnp.int32, one_chip),
            _sds((rows // bm,), jnp.int32, one_chip),
            _sds((1,), jnp.int32, one_chip))
    hlo = f.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_mellum_decode_reads_expert_codes_in_place(one_chip):
    """The served decode closure (32 slots, max_seq 2048, 8 layers at
    published widths) compiled for the chip: the grouped kernel is called
    twice per layer step and no op slices a layer out of the [8, 64, ...]
    int8 expert stacks."""
    import dataclasses
    import re

    from bench import system
    from repro.core.program import MappingPlan, program_model
    from repro.runtime.engine import ServeEngine

    conf = system.load_config("mellum2-12b-aimc")
    spec, cfg = system.transformer_config(conf)
    model = spec.model_module()
    exe = system.execution(conf)
    # the described chip is not the backend: pin the kernel
    exe = dataclasses.replace(exe, aimc=dataclasses.replace(
        exe.aimc, impl="pallas_tpu"))

    def make(key):
        params = model.init(key, cfg)
        return params, program_model(params, MappingPlan(), exe.aimc)

    params, program = jax.eval_shape(make, jax.ShapeDtypeStruct(
        (2,), jnp.uint32))
    installed = program.install(params)

    def placed(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    eng_conf = conf["engine"]
    eng = ServeEngine(model, cfg, exe, placed(installed),
                      n_slots=eng_conf["n_slots"],
                      prompt_pad=eng_conf["prompt_pad"],
                      max_seq=eng_conf["max_seq"], cache_dtype=jnp.float32,
                      family=spec.family, module=spec.module,
                      decode_chunk=1)
    hlo = eng._decode_jits[1].lower(
        eng.params, placed(jax.eval_shape(eng._empty_cache)),
        placed(jax.eval_shape(eng._empty_tok_buf)),
        placed(jax.eval_shape(eng._empty_state))).compile().as_text()
    calls = re.findall(r"%aimc_expert_pallas[\w.]* = ", hlo)
    assert len(calls) == 2
    # a layer's slice of a stack would be an [64, KB, 512, Np] int8 result
    sliced = re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = s8\[(?:1,)?64,", hlo,
                        re.M)
    assert not sliced, sliced[:3]
