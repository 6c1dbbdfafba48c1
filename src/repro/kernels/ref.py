"""Pure-jnp oracle for the AIMC crossbar MVM (the reference the Pallas kernel
must match bit-for-bit in tests).

Interface contract (shared with kernels/aimc_mvm.py and kernels/ops.py):

  x          f32/bf16 [B, KB*M]   activations, K already zero-padded to a
                                  whole number of row blocks
  w_q        int8     [KB, M, Np] programmed conductance codes, one row block
                                  per physical-tile row span (zero padded)
  s_w        f32      [KB, Np]    per (row-block, bit-line) weight scale, with
                                  drift gain / compensation already folded in
  s_x        f32      [1, 1]      DAC input scale (fixed or per-call max-abs)
  read_noise f32      [KB, B, Np] additive bit-line noise in accumulator LSBs
                                  (zeros when the noise model is disabled)
  adc_step   float    (static)    ADC step in accumulator LSBs (quant.adc_step_lsb)

Returns f32 [B, Np]:  sum over row blocks of
    ADC8(x_q_block @ w_q_block + noise) * adc_step * s_x * s_w_block
which is exactly the paper's data flow: CM_QUEUE (DAC quantize) ->
CM_PROCESS (analog MAC + ADC) -> CM_DEQUEUE + digital accumulate/cast.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quant import adc_quantize, quantize
from repro.kernels import cprng

EPILOGUE_FNS = {
    "none": lambda y: y,
    "relu": lambda y: jnp.maximum(y, 0.0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
}


def aimc_matmul_ref(x, w_q, s_w, s_x, read_noise, *, adc_step: float) -> jnp.ndarray:
    if x.ndim != 2 or w_q.ndim != 3:
        raise ValueError(f"bad ranks: x{x.shape} w_q{w_q.shape}")
    kb, m, np_ = w_q.shape
    b = x.shape[0]
    if x.shape[1] != kb * m:
        raise ValueError(f"x K={x.shape[1]} != KB*M={kb * m}")

    x_blocks = x.reshape(b, kb, m).astype(jnp.float32)
    x_q = quantize(x_blocks, s_x.reshape(()))                       # int8 [B,KB,M]
    acc = jnp.einsum(
        "bkm,kmn->kbn",
        x_q.astype(jnp.int32),
        w_q.astype(jnp.int32),
    ).astype(jnp.float32)                                           # [KB,B,Np]
    acc = acc + read_noise
    codes = adc_quantize(acc, jnp.float32(adc_step))                # int32 [KB,B,Np]
    contrib = codes.astype(jnp.float32) * s_w[:, None, :]           # [KB,B,Np]
    y = jnp.sum(contrib, axis=0) * (jnp.float32(adc_step) * s_x.reshape(()))
    return y.astype(jnp.float32)


def aimc_matmul_ref_v2(x, w_q, s_w, s_x, seed=None, bias=None, *,
                       adc_step: float, sigma: float = 0.0,
                       activation: str = "none") -> jnp.ndarray:
    """Kernel-v2 oracle: counter-addressed in-kernel noise + fused epilogue.

    Noise is materialized here (the oracle's whole point is bulk-array
    clarity) through the SAME `cprng` counter math the Pallas kernel runs
    per tile, so kernel and oracle are bit-identical for a given seed. The
    epilogue (bias + activation) is the identical f32 arithmetic the kernel
    applies on its last row-block step.
    """
    kb, m, np_ = w_q.shape
    b = x.shape[0]
    if sigma > 0.0:
        if seed is None:
            raise ValueError("sigma > 0 requires a seed")
        noise = sigma * cprng.read_noise_array(seed, kb, b, np_)
    else:
        noise = jnp.zeros((kb, b, np_), jnp.float32)
    y = aimc_matmul_ref(x, w_q, s_w, s_x, noise, adc_step=adc_step)
    if bias is not None:
        y = y + bias.reshape(1, np_).astype(jnp.float32)
    return EPILOGUE_FNS[activation](y)


def aimc_matmul_stacked_ref(x, w_q, s_w, s_x, seed=None, bias=None, *,
                            adc_step: float, sigma: float = 0.0,
                            activations="none") -> jnp.ndarray:
    """Gate-fused stack oracle: per-gate v2 calls under `stack_seed` — the
    bit-equality target for `aimc_matmul_pallas_stacked`."""
    g_, kb, m, np_ = w_q.shape
    if isinstance(activations, str):
        activations = (activations,) * g_
    outs = []
    for g in range(g_):
        seed_g = cprng.stack_seed(seed, g) if seed is not None else None
        outs.append(aimc_matmul_ref_v2(
            x, w_q[g], s_w[g], s_x, seed_g,
            bias[g] if bias is not None else None,
            adc_step=adc_step, sigma=sigma, activation=activations[g]))
    return jnp.stack(outs)


def aimc_expert_ref(x, w_qs, s_ws, s_x, layer, block_expert, n_used, *,
                    adc_step: float, block_rows: int) -> jnp.ndarray:
    """Grouped expert oracle (`aimc_mvm.aimc_expert_pallas`): row block i
    of ``x`` [R, KB*M] runs through expert ``block_expert[i]`` of each
    stack ``w_qs[g]`` [L, E, KB, M, Np] at ``layer``, quantised under that
    expert's DAC scale ``s_x[e]``; blocks from ``n_used`` on come out zero.
    Returns f32 [G, R, Np]."""
    r, _ = x.shape
    nb = r // block_rows
    blocks = jnp.asarray(block_expert, jnp.int32)
    row_expert = jnp.repeat(blocks, block_rows)                     # [R]
    live = (jnp.arange(nb) < jnp.asarray(n_used).reshape(()))
    live = jnp.repeat(live, block_rows)[:, None]
    outs = []
    for w_q, s_w in zip(w_qs, s_ws):
        _, _, kb, m, np_ = w_q.shape
        w = jnp.take(w_q[layer], blocks, axis=0)              # [nb, KB, M, Np]
        sw = jnp.take(s_w[layer], blocks, axis=0)             # [nb, KB, Np]
        sx = jnp.take(s_x, row_expert).reshape(nb, block_rows, 1, 1)
        xb = x.reshape(nb, block_rows, kb, m).astype(jnp.float32)
        x_q = jnp.clip(jnp.round(xb / sx), -127, 127).astype(jnp.int32)
        acc = jnp.einsum("ibkm,ikmn->ikbn", x_q, w.astype(jnp.int32))
        codes = adc_quantize(acc, jnp.float32(adc_step)).astype(jnp.float32)
        y = jnp.sum(codes * sw[:, :, None, :], axis=1)        # [nb, bm, Np]
        y = y * (jnp.float32(adc_step) * sx[:, :, 0])
        outs.append(jnp.where(live, y.reshape(r, np_), 0.0))
    return jnp.stack(outs)


def digital_matmul_ref(x, w, out_dtype=jnp.float32):
    """The digital (CPU/SIMD) baseline the paper compares against: a plain
    full-precision matmul."""
    return jnp.asarray(x @ w, dtype=out_dtype)
