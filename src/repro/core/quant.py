"""DAC/ADC quantization math for AIMC crossbar simulation.

This module is the single source of truth for the fixed-point arithmetic of the
simulated AIMC tile (paper §III-B):

  * DAC: signed 8-bit input quantization. The input scaling factor is either
    computed per-call ("dynamic", max-abs) or fixed ("static") as the paper
    recommends ("preferably fixed to avoid dynamic scaling").
  * Crossbar: int8 x int8 -> int32 exact MAC (the analog dot product, modelled
    noiselessly here; noise lives in `core.noise`).
  * ADC: signed 8-bit output quantization with a per-tile output step sized to
    the statistical (not worst-case) bit-line range, `adc_alpha * sqrt(M) * 127`
    accumulator LSBs for an M-row tile.

All functions are pure jnp and are safe to call inside Pallas kernel bodies,
so the Pallas kernel (`kernels/aimc_mvm.py`) and the oracle (`kernels/ref.py`)
share literally the same arithmetic.
"""

from __future__ import annotations

import jax.numpy as jnp

# Signed 8-bit converters (paper: "The resolution of DACs and ADCs are signed
# 8-bits"). We use the symmetric range [-127, 127] so that a weight and its
# negation program to exactly opposite conductance pairs.
QMAX = 127
QMIN = -127


def sym_scale(x: jnp.ndarray, axis=None, eps: float = 1e-12) -> jnp.ndarray:
    """Symmetric max-abs quantization scale so x/scale fits in [-127, 127].

    Written as the multiply by 1/127 that XLA turns ``amax / 127`` into
    under ``jit``: eager and compiled calls then agree on the scale."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    return jnp.maximum(amax, eps) * (1.0 / QMAX)


def quantize(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Round-to-nearest signed-8-bit quantization (returns int8)."""
    q = jnp.round(x / scale)
    return jnp.clip(q, QMIN, QMAX).astype(jnp.int8)


def dequantize(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    return q.astype(dtype) * scale.astype(dtype)


def adc_step_lsb(tile_rows: int, adc_alpha: float) -> float:
    """ADC quantization step, in int32-accumulator LSBs.

    The bit line of an M-row tile accumulates up to M*127*127 LSBs worst case,
    but activations concentrate, so real designs size the ADC full scale to the
    statistical range ~ sqrt(M) * 127 * 127 (cf. HERMES [13]). With an 8-bit
    ADC (127 positive codes) the step is alpha * sqrt(M) * 127 LSBs.
    """
    return float(max(1.0, adc_alpha * (tile_rows ** 0.5) * QMAX))


def quantize_weight_int8(w: jnp.ndarray):
    """Per-output-channel symmetric int8 quantization of a [..., K, N] weight.

    Returns {"q": int8 codes, "s": f32 scales [..., 1, N]} — the paper's
    number format for serving (`Execution.serve_int8`), consumed by
    `models.layers.as_weight`."""
    s = sym_scale(w.astype(jnp.float32), axis=-2)          # [..., 1, N]
    return {"q": quantize(w.astype(jnp.float32), s), "s": s}


def quantize_params_int8(params, quantizable: set[str], skip=("embed",)):
    """Tree-wide int8 packing of the projection matrices named in
    `quantizable` (see launch.shardings name sets); other leaves cast to
    bf16. Mirrors launch.steps._serve_params_shape."""
    import jax

    def conv(path, leaf):
        name = ""
        for k in reversed(path):
            if hasattr(k, "key"):
                name = str(k.key)
                break
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        if name in quantizable and name not in skip and leaf.ndim >= 2:
            return quantize_weight_int8(leaf)
        return leaf.astype(jnp.bfloat16)

    return jax.tree_util.tree_map_with_path(conv, params)


def adc_quantize(acc: jnp.ndarray, step: jnp.ndarray) -> jnp.ndarray:
    """Quantize an int32 (or float) bit-line accumulation to signed 8-bit codes.

    Returns int32 codes in [-127, 127] (int32 so downstream digital accumulation
    of multiple row-block tiles does not overflow).
    """
    q = jnp.round(acc.astype(jnp.float32) / step)
    return jnp.clip(q, QMIN, QMAX).astype(jnp.int32)
