"""Pallas TPU kernels: fused AIMC crossbar matmul (v1 legacy + kernel v2).

This is the "tightly-coupled" execution of the paper translated to TPU terms:
DAC quantization, the int8 crossbar MAC, bit-line read noise, ADC quantization
and the digital per-row-block accumulation all happen in ONE kernel, so no
analog-domain intermediate (x_q, bit-line accumulations, ADC codes) ever
round-trips to HBM — the TPU analogue of not crossing the I/O bus.

Kernel v2 (`aimc_matmul_pallas_v2`, `aimc_matmul_pallas_stacked`) closes the
three leaks v1 still had around the fused MAC:

  * in-kernel read noise — v1 streamed a `[KB, B, Np]` f32 noise tensor from
    HBM (4x the bytes of the int8 weight panel at square shapes, streamed
    even as zeros when noise was off). v2 takes a scalar-prefetched uint32
    seed instead and draws the noise in VMEM: counter mode (`kernels/cprng`,
    bit-identical to the oracle, the CI path) or the TPU hardware PRNG
    (`pltpu.prng_seed`/`prng_random_bits`, seeded per grid cell;
    `noise_source="hw"`, compiled TPU only).
  * fused epilogue — bias add + a statically-selected activation
    (`relu`/`sigmoid`/`tanh`/`none`) run on the last row-block grid step,
    while the output block is still VMEM-resident, so the per-layer output
    leaves the kernel finished instead of round-tripping through a separate
    XLA bias/activation op.
  * gate-fused multi-MVM — a `[G, KB, M, Np]` stack (LSTM's four gates,
    attention QKV, gate/up FFN pairs) runs as ONE weight-stationary
    `pallas_call` sharing the input and its single DAC scale, with a per-gate
    epilogue. Slice g draws noise under `cprng.stack_seed(seed, g)`, so the
    stack is bit-equal to per-gate v2 calls.

The grouped expert kernel (`aimc_expert_pallas`) serves a sparse-expert
layer whose `[L, E, KB, M, Np]` expert stacks stay where they were
programmed: input rows arrive sorted by expert, each expert's rows padded to
whole row blocks, and scalar-prefetched ids (the layer, each row block's
expert, the number of blocks in use) select every block's codes and scales
straight from the stacks, so nothing slices a layer or an expert out of
them. One call runs G same-shape expert matrices that share an input (the
gate and up projections), each row block through the same DAC -> int8 MAC
-> ADC steps as v2 under its expert's DAC scale.

Grid: (B/bB, Np/bN, KB) — (G, B/bB, Np/bN, KB) stacked — with the row-block
dimension innermost so the f32 output block [bB, bN] is revisited
consecutively and accumulated in place. The int8 weight row-block panel
[1, M, bN] is the *stationary* operand: 2-4x smaller than a bf16/fp32 weight
panel (the TPU mirror of the paper's working-set collapse), and for decode
(B <= bB) it is streamed from HBM exactly once.

MXU alignment: M (tile rows) and bN are multiples of 128; the int8 x int8
contraction uses preferred_element_type=int32 to engage the MXU int8 path.
VMEM working set per step: x block bB*M f32 + weight panel M*bN int8 + out
block — v2 carries no noise block — sized well under 16 MB for the default
(bB=128, M=512, bN=512).

Block shapes keep Mosaic's tiling rule: the last two dims of every block
are (8, 128)-divisible or span the array. Per-column rows (`s_w`, `bias`)
therefore get a unit axis (`[KB, 1, Np]`, block `(1, 1, bN)`) in the
wrappers below. The kernels compile by default; tests run them with
`interpret=True` against kernels/ref.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # TPU-only module; present in the baked toolchain, absent on bare CPU
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from repro.core.quant import QMAX, QMIN
from repro.kernels import cprng
# One epilogue table for kernel and oracle: what the kernel applies on its
# last grid step is literally what the unfused fallback applies after it.
from repro.kernels.ref import EPILOGUE_FNS as _ACT_FNS

EPILOGUES = ("none", "relu", "sigmoid", "tanh")
# The int8 MAC is exact at any precision, and Mosaic lowers an int8 dot only
# at DEFAULT: pinned, so a caller's float32 matmul precision
# (`Execution.matmul_precision`) never reaches it.
_INT8_MAC = jax.lax.Precision.DEFAULT
NOISE_SOURCES = ("counter", "hw")


def _check_epilogue(activation: str) -> None:
    if activation not in EPILOGUES:
        raise ValueError(
            f"unknown epilogue {activation!r}; expected one of {EPILOGUES}")


# ---------------------------------------------------------------------------
# v1 kernel — legacy contract with an explicit HBM noise operand. Kept for
# the staged/loose comparisons and the v1 differential tests; the execution
# path (`core.aimc.aimc_apply`) no longer uses it.
# ---------------------------------------------------------------------------


def _aimc_mvm_kernel(x_ref, w_ref, sw_ref, sx_ref, noise_ref, o_ref, *, adc_step: float):
    k = pl.program_id(2)

    # ---- DAC: signed-8-bit input quantization (CM_QUEUE) -------------------
    s_x = sx_ref[0, 0]
    x_q = jnp.clip(jnp.round(x_ref[...] / s_x), QMIN, QMAX).astype(jnp.int8)

    # ---- crossbar: int8 x int8 -> int32 bit-line MAC (CM_PROCESS) ----------
    acc = jax.lax.dot_general(
        x_q,
        w_ref[0],
        (((1,), (0,)), ((), ())),
        precision=_INT8_MAC,
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)
    acc = acc + noise_ref[0]

    # ---- ADC: signed-8-bit output quantization ------------------------------
    codes = jnp.clip(jnp.round(acc / adc_step), QMIN, QMAX)

    # ---- digital: dequant + per-row-block accumulate (CM_DEQUEUE + cast) ----
    contrib = codes * (sw_ref[0] * (adc_step * s_x))

    @pl.when(k == 0)
    def _init():
        o_ref[...] = contrib

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += contrib


@functools.partial(
    jax.jit,
    static_argnames=("adc_step", "block_b", "block_n", "interpret"),
)
def aimc_matmul_pallas(
    x, w_q, s_w, s_x, read_noise, *,
    adc_step: float,
    block_b: int = 128,
    block_n: int = 512,
    interpret: bool = False,
):
    kb, m, np_ = w_q.shape
    b = x.shape[0]
    bb = min(block_b, b)
    bn = min(block_n, np_)
    if b % bb or np_ % bn:
        raise ValueError(f"B={b} / Np={np_} not divisible by blocks ({bb},{bn})")

    grid = (b // bb, np_ // bn, kb)
    return pl.pallas_call(
        functools.partial(_aimc_mvm_kernel, adc_step=float(adc_step)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, m), lambda i, j, k: (i, k)),          # x
            pl.BlockSpec((1, m, bn), lambda i, j, k: (k, 0, j)),    # w_q (stationary panel)
            pl.BlockSpec((1, 1, bn), lambda i, j, k: (k, 0, j)),    # s_w
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),           # s_x
            pl.BlockSpec((1, bb, bn), lambda i, j, k: (k, i, j)),   # read noise
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, np_), jnp.float32),
        interpret=interpret,
    )(x.astype(jnp.float32), w_q, s_w.reshape(kb, 1, np_), s_x, read_noise)


# ---------------------------------------------------------------------------
# kernel v2 — in-kernel PRNG noise + fused epilogue
# ---------------------------------------------------------------------------


def _in_kernel_noise(seed, off_ref, k, i, j, grid_dims, bb: int, bn: int,
                     b_total: int, n_total: int, noise_source: str):
    """One [bb, bn] tile of read noise, generated in VMEM (never from HBM).

    `off_ref` holds (row0, col0, shard) of this call within a sharded MVM
    (`offsets` of the front doors): counters address global rows and
    columns, and each shard seeds its own range of hardware PRNG cells."""
    if noise_source == "counter":
        return cprng.noise_tile(seed, k, off_ref[0] + i * bb,
                                off_ref[1] + j * bn, bb, bn, b_total, n_total)
    # hardware PRNG (compiled TPU only): a distinct stream per grid cell.
    cell, n_cells = jnp.int32(0), 1
    for pid, extent in grid_dims:
        cell = cell * jnp.int32(extent) + pid
        n_cells *= extent
    pltpu.prng_seed(seed.astype(jnp.int32) + off_ref[2] * n_cells + cell)
    h1 = pltpu.bitcast(pltpu.prng_random_bits((bb, bn)), jnp.uint32)
    h2 = pltpu.bitcast(pltpu.prng_random_bits((bb, bn)), jnp.uint32)
    u1 = (cprng.to_f32(h1 >> 8) + 1.0) * jnp.float32(2 ** -24)
    u2 = cprng.to_f32(h2 >> 8) * jnp.float32(2 ** -24)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(
        jnp.float32(6.283185307179586) * u2)


def _mac_adc_contrib(x_blk, w_panel, sw_row, s_x, noise, adc_step: float):
    """DAC -> int8 MAC -> (+noise) -> ADC -> dequant: one row-block contrib."""
    x_q = jnp.clip(jnp.round(x_blk / s_x), QMIN, QMAX).astype(jnp.int8)
    acc = jax.lax.dot_general(
        x_q, w_panel, (((1,), (0,)), ((), ())), precision=_INT8_MAC,
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)
    if noise is not None:
        acc = acc + noise
    codes = jnp.clip(jnp.round(acc / adc_step), QMIN, QMAX)
    return codes * (sw_row * (adc_step * s_x))


def _aimc_mvm_kernel_v2(seed_ref, off_ref, x_ref, w_ref, sw_ref, sx_ref, *rest,
                        adc_step: float,
                        sigma: float, activation: str, has_bias: bool,
                        grid_bij: tuple[int, int, int], b_total: int,
                        n_total: int, noise_source: str):
    bias_ref = rest[0] if has_bias else None
    o_ref = rest[-1]
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kb = grid_bij[2]
    bb, bn = o_ref.shape

    noise = None
    if sigma > 0.0:
        grid_dims = ((i, grid_bij[0]), (j, grid_bij[1]), (k, kb))
        noise = sigma * _in_kernel_noise(seed_ref[0], off_ref, k, i, j,
                                         grid_dims, bb, bn, b_total, n_total,
                                         noise_source)
    s_x = sx_ref[0, 0]
    contrib = _mac_adc_contrib(x_ref[...], w_ref[0], sw_ref[0], s_x, noise,
                               adc_step)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = contrib

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += contrib

    if has_bias or activation != "none":
        @pl.when(k == kb - 1)
        def _epilogue():
            y = o_ref[...]
            if has_bias:
                y = y + bias_ref[...]
            o_ref[...] = _ACT_FNS[activation](y)


@functools.partial(
    jax.jit,
    static_argnames=("adc_step", "sigma", "activation", "block_b", "block_n",
                     "noise_source", "interpret", "b_logical", "n_logical"),
)
def aimc_matmul_pallas_v2(
    x, w_q, s_w, s_x, seed=None, bias=None, *,
    adc_step: float,
    sigma: float = 0.0,
    activation: str = "none",
    block_b: int = 128,
    block_n: int = 512,
    noise_source: str = "counter",
    interpret: bool = False,
    b_logical: int | None = None,
    offsets=None,
    n_logical: int | None = None,
):
    """Kernel v2 front door (block-aligned shapes; `ops.aimc_matmul_v2` pads).

    `seed` is a scalar uint32 array consumed via scalar prefetch; `sigma` the
    static read-noise std in accumulator LSBs (0.0 compiles the noise code
    out entirely). `bias` is a `[1, Np]` f32 row added on the last row-block
    step; `activation` one of `EPILOGUES`. `b_logical` is the pre-padding
    batch, addressing noise counters so padded rows never shift real draws.
    A shard of a larger MVM passes that MVM's `b_logical`/`n_logical` and
    its int32 `offsets` (first row, first column, shard index) so its noise
    is the slice the whole call would draw; the default is an unsharded
    call at (0, 0, 0).
    """
    _check_epilogue(activation)
    if noise_source not in NOISE_SOURCES:
        raise ValueError(f"unknown noise_source {noise_source!r}")
    kb, m, np_ = w_q.shape
    b = x.shape[0]
    bb = min(block_b, b)
    bn = min(block_n, np_)
    if b % bb or np_ % bn:
        raise ValueError(f"B={b} / Np={np_} not divisible by blocks ({bb},{bn})")
    if seed is None:
        if sigma > 0.0:
            raise ValueError("sigma > 0 requires a seed")
        seed = jnp.zeros((1,), jnp.uint32)
    else:
        seed = jnp.asarray(seed).reshape((1,)).astype(jnp.uint32)
    offsets = (jnp.zeros((3,), jnp.int32) if offsets is None
               else jnp.asarray(offsets, jnp.int32).reshape((3,)))

    grid = (b // bb, np_ // bn, kb)
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((bb, m), lambda i, j, k, s, o: (i, k)),          # x
        pl.BlockSpec((1, m, bn), lambda i, j, k, s, o: (k, 0, j)),    # w_q panel
        pl.BlockSpec((1, 1, bn), lambda i, j, k, s, o: (k, 0, j)),    # s_w
        pl.BlockSpec((1, 1), lambda i, j, k, s, o: (0, 0)),           # s_x
    ]
    operands = [x.astype(jnp.float32), w_q, s_w.reshape(kb, 1, np_), s_x]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k, s, o: (0, j)))
        operands.append(bias.reshape(1, np_).astype(jnp.float32))

    kernel = functools.partial(
        _aimc_mvm_kernel_v2,
        adc_step=float(adc_step), sigma=float(sigma), activation=activation,
        has_bias=has_bias, grid_bij=grid,
        b_total=int(b_logical if b_logical is not None else b),
        n_total=int(n_logical if n_logical is not None else np_),
        noise_source=noise_source)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k, s, o: (i, j)))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, np_), jnp.float32),
        interpret=interpret,
    )(seed, offsets, *operands)


# ---------------------------------------------------------------------------
# kernel v2 — gate-fused stacked multi-MVM
# ---------------------------------------------------------------------------


def _aimc_mvm_kernel_stacked(seed_ref, off_ref, x_ref, w_ref, sw_ref, sx_ref,
                             *rest,
                             adc_step: float, sigma: float,
                             activations: tuple[str, ...], has_bias: bool,
                             grid_gbij: tuple[int, int, int, int],
                             b_total: int, n_total: int, noise_source: str):
    bias_ref = rest[0] if has_bias else None
    o_ref = rest[-1]
    g, i, j, k = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                  pl.program_id(3))
    kb = grid_gbij[3]
    _, bb, bn = o_ref.shape

    noise = None
    if sigma > 0.0:
        seed_g = cprng.stack_seed(seed_ref[0], g)
        grid_dims = ((g, grid_gbij[0]), (i, grid_gbij[1]),
                     (j, grid_gbij[2]), (k, kb))
        noise = sigma * _in_kernel_noise(seed_g, off_ref, k, i, j, grid_dims,
                                         bb, bn, b_total, n_total,
                                         noise_source)
    s_x = sx_ref[0, 0]
    contrib = _mac_adc_contrib(x_ref[...], w_ref[0, 0], sw_ref[0, 0], s_x,
                               noise, adc_step)

    @pl.when(k == 0)
    def _init():
        o_ref[0] = contrib

    @pl.when(k > 0)
    def _acc():
        o_ref[0] += contrib

    if has_bias or any(a != "none" for a in activations):
        @pl.when(k == kb - 1)
        def _epilogue():
            y = o_ref[0]
            if has_bias:
                y = y + bias_ref[0]
            if len(set(activations)) == 1:
                o_ref[0] = _ACT_FNS[activations[0]](y)
            else:
                # per-gate epilogue: one guarded write per distinct gate
                for gi, act in enumerate(activations):
                    @pl.when(g == gi)
                    def _write(y=y, act=act):
                        o_ref[0] = _ACT_FNS[act](y)


@functools.partial(
    jax.jit,
    static_argnames=("adc_step", "sigma", "activations", "block_b", "block_n",
                     "noise_source", "interpret", "b_logical", "n_logical"),
)
def aimc_matmul_pallas_stacked(
    x, w_q, s_w, s_x, seed=None, bias=None, *,
    adc_step: float,
    sigma: float = 0.0,
    activations: tuple[str, ...] | str = "none",
    block_b: int = 128,
    block_n: int = 512,
    noise_source: str = "counter",
    interpret: bool = False,
    b_logical: int | None = None,
    offsets=None,
    n_logical: int | None = None,
):
    """Gate-fused multi-MVM: `[G, KB, M, Np]` weights, one shared `[B, K]`
    input and DAC scale, `[G, B, Np]` out — ONE weight-stationary
    `pallas_call` for the whole gate/head stack. `activations` is one
    epilogue for all gates or a per-gate tuple of length G; slice g draws
    noise under `cprng.stack_seed(seed, g)`. `b_logical`, `n_logical` and
    `offsets` place a shard as in `aimc_matmul_pallas_v2`."""
    g_, kb, m, np_ = w_q.shape
    if isinstance(activations, str):
        activations = (activations,) * g_
    activations = tuple(activations)
    if len(activations) != g_:
        raise ValueError(f"{len(activations)} activations for G={g_} gates")
    for a in activations:
        _check_epilogue(a)
    if noise_source not in NOISE_SOURCES:
        raise ValueError(f"unknown noise_source {noise_source!r}")
    b = x.shape[0]
    bb = min(block_b, b)
    bn = min(block_n, np_)
    if b % bb or np_ % bn:
        raise ValueError(f"B={b} / Np={np_} not divisible by blocks ({bb},{bn})")
    if seed is None:
        if sigma > 0.0:
            raise ValueError("sigma > 0 requires a seed")
        seed = jnp.zeros((1,), jnp.uint32)
    else:
        seed = jnp.asarray(seed).reshape((1,)).astype(jnp.uint32)
    offsets = (jnp.zeros((3,), jnp.int32) if offsets is None
               else jnp.asarray(offsets, jnp.int32).reshape((3,)))

    grid = (g_, b // bb, np_ // bn, kb)
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((bb, m), lambda g, i, j, k, s, o: (i, k)),           # x (shared)
        pl.BlockSpec((1, 1, m, bn), lambda g, i, j, k, s, o: (g, k, 0, j)),
        pl.BlockSpec((1, 1, 1, bn), lambda g, i, j, k, s, o: (g, k, 0, j)),
        pl.BlockSpec((1, 1), lambda g, i, j, k, s, o: (0, 0)),            # s_x
    ]
    operands = [x.astype(jnp.float32), w_q, s_w.reshape(g_, kb, 1, np_), s_x]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, bn), lambda g, i, j, k, s, o: (g, 0, j)))
        operands.append(bias.reshape(g_, 1, np_).astype(jnp.float32))

    kernel = functools.partial(
        _aimc_mvm_kernel_stacked,
        adc_step=float(adc_step), sigma=float(sigma), activations=activations,
        has_bias=has_bias, grid_gbij=grid,
        b_total=int(b_logical if b_logical is not None else b),
        n_total=int(n_logical if n_logical is not None else np_),
        noise_source=noise_source)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bb, bn), lambda g, i, j, k, s, o: (g, i, j)))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g_, b, np_), jnp.float32),
        interpret=interpret,
    )(seed, offsets, *operands)


# ---------------------------------------------------------------------------
# grouped expert kernel — expert stacks read in place
# ---------------------------------------------------------------------------


def _aimc_expert_kernel(layer_ref, expert_ref, used_ref, x_ref, *refs,
                        adc_step: float, n_gates: int, kb: int, m: int):
    """One (column block, row block) cell: every gate's [bm, bN] output of
    the row block's expert, or zeros past the blocks in use."""
    w_refs, sw_refs = refs[:n_gates], refs[n_gates:2 * n_gates]
    sx_ref, o_ref = refs[2 * n_gates], refs[-1]
    i = pl.program_id(1)

    @pl.when(i < used_ref[0])
    def _compute():
        s_x = sx_ref[0, 0, 0]
        for g in range(n_gates):
            acc = None
            for k in range(kb):
                contrib = _mac_adc_contrib(
                    x_ref[:, k * m:(k + 1) * m], w_refs[g][0, 0, k],
                    sw_refs[g][0, 0, pl.ds(k, 1), :], s_x, None, adc_step)
                acc = contrib if acc is None else acc + contrib
            o_ref[g] = acc

    # idle blocks repeat the last used expert's id: their rows come out zero
    # so that they leave its DAC scale in the next call as it is
    @pl.when(i >= used_ref[0])
    def _idle():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("adc_step", "block_rows", "block_n",
                              "interpret"))
def aimc_expert_pallas(x, w_qs, s_ws, s_x, layer, block_expert, n_used, *,
                       adc_step: float, block_rows: int, block_n: int = 512,
                       interpret: bool = False):
    """Grouped crossbar MVM over expert stacks read in place.

    x          f32 [R, KB*M]   rows sorted by expert, each expert's rows
                               padded to whole blocks of ``block_rows``
    w_qs       G int8 stacks [L, E, KB, M, Np] (same shape)
    s_ws       G f32 stacks [L, E, KB, Np]
    s_x        f32 [E] the DAC scale of each expert's rows
    layer      int32 [1] the layer the stacks are read at
    block_expert int32 [R / block_rows] the expert of each row block
    n_used     int32 [1] the row blocks that hold rows; later blocks come
               out zero, and their expert should repeat the last used one
               so that no codes are fetched for them
    -> f32 [G, R, Np].

    Grid (Np/bN, R/bm): for one column block the row blocks run in order,
    so consecutive blocks of one expert keep its [KB, M, bN] panel of
    codes in VMEM and each touched expert's codes are read once."""
    g_ = len(w_qs)
    r, kp = x.shape
    _, e, kb, m, np_ = w_qs[0].shape
    bm, bn = block_rows, block_n
    if r % bm or np_ % bn or kp != kb * m:
        raise ValueError(f"x {x.shape} / Np={np_} do not fit blocks "
                         f"({bm}, {bn}) of {kb}x{m} rows")
    grid = (np_ // bn, r // bm)
    w_spec = pl.BlockSpec((1, 1, kb, m, bn),
                          lambda j, i, lyr, be, nu: (lyr[0], be[i], 0, 0, j))
    sw_spec = pl.BlockSpec((1, 1, kb, bn),
                           lambda j, i, lyr, be, nu: (lyr[0], be[i], 0, j))
    in_specs = ([pl.BlockSpec((bm, kp), lambda j, i, lyr, be, nu: (i, 0))]
                + [w_spec] * g_ + [sw_spec] * g_
                + [pl.BlockSpec((1, 1, 1),
                                lambda j, i, lyr, be, nu: (be[i], 0, 0))])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((g_, bm, bn),
                               lambda j, i, lyr, be, nu: (0, i, j)))
    kernel = functools.partial(_aimc_expert_kernel, adc_step=float(adc_step),
                               n_gates=g_, kb=kb, m=m)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g_, r, np_), jnp.float32),
        interpret=interpret, name="aimc_expert_pallas",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(block_expert, jnp.int32),
      jnp.asarray(n_used, jnp.int32).reshape(1),
      x.astype(jnp.float32), *w_qs, *s_ws,
      s_x.reshape(e, 1, 1).astype(jnp.float32))
