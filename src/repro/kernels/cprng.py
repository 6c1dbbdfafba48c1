"""Counter-based Gaussian PRNG shared by the Pallas kernel and the oracle.

Kernel v2 generates read noise *inside* the fused MVM kernel, so the
`[KB, B, Np]` f32 noise tensor of kernel v1 — often larger than the int8
weight panel it rode along with — never exists in HBM. Two generators back
that contract:

  * counter mode (this module) — every noise element is a pure function of
    (seed, global element counter): a lowbias32 integer hash feeding a
    Box-Muller transform. The math is plain `jnp` elementwise arithmetic on
    `uint32`/`float32`, legal both inside a Pallas kernel body and as a bulk
    array computation, so `kernels/ref.py` and the interpret-mode kernel
    produce BIT-IDENTICAL noise for the same seed — block shape and grid
    layout cannot change a single draw. This is the default and the one CI
    exercises.
  * hardware mode (`kernels/aimc_mvm.py`, TPU only) — `pltpu.prng_seed` /
    `pltpu.prng_random_bits`, seeded per grid cell. Faster on silicon, but
    only statistically equivalent to the oracle; gated behind
    `noise_source="hw"` + the compiled TPU impl.

The element counter of a `[KB, B, Np]` noise tensor is the row-major flat
index `(k * B + b) * Np + c` in uint32 (wrapping) arithmetic; gate `g` of a
stacked multi-MVM re-seeds via `stack_seed(seed, g)`, so the fused stack and
per-gate calls with the derived seeds draw identical noise.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

# 2^32 / phi — the classic Weyl increment, used to decorrelate seed streams.
GOLDEN = 0x9E3779B9
_U24 = float(2 ** -24)
_TWO_PI = 6.283185307179586


def _u32(v) -> jnp.ndarray:
    return jnp.asarray(v).astype(jnp.uint32)


def mix32(x: jnp.ndarray) -> jnp.ndarray:
    """lowbias32 (Degski/Evensen) avalanche hash on uint32 lanes.

    Elementwise xor/shift/multiply only — everything Mosaic and the
    interpreter lower identically, with deterministic uint32 wraparound.
    """
    x = _u32(x)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def to_f32(v: jnp.ndarray) -> jnp.ndarray:
    """uint32 -> f32 for values below 2**24, through int32: Mosaic has no
    direct uint32 -> f32 cast, and both steps are exact in that range."""
    return v.astype(jnp.int32).astype(jnp.float32)


def stack_seed(seed: jnp.ndarray, g) -> jnp.ndarray:
    """Per-gate seed of slice `g` in a stacked multi-MVM.

    A per-gate single-matrix call with `stack_seed(seed, g)` draws exactly
    the noise the fused `[G, ...]` stack draws for slice g."""
    return mix32(_u32(seed) ^ (_u32(g) + jnp.uint32(1)) * jnp.uint32(GOLDEN))


def gauss_from_counter(seed: jnp.ndarray, ctr: jnp.ndarray) -> jnp.ndarray:
    """Standard-normal f32 draws, one per uint32 counter element.

    Two chained hash streams feed a Box-Muller transform; u1 lands in
    (0, 1] (so the log is finite) and u2 in [0, 1). 24-bit uniforms are
    exact in f32.
    """
    h1 = mix32(_u32(ctr) ^ _u32(seed))
    h2 = mix32(h1 + jnp.uint32(GOLDEN))
    u1 = (to_f32(h1 >> 8) + 1.0) * jnp.float32(_U24)
    u2 = to_f32(h2 >> 8) * jnp.float32(_U24)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(jnp.float32(_TWO_PI) * u2)


def noise_tile(seed, k, row0, col0, bb: int, bn: int,
               b_total: int, n_total: int) -> jnp.ndarray:
    """One `[bb, bn]` tile of the virtual `[KB, B, Np]` noise tensor.

    `k` is the row-block index (traced), `row0`/`col0` the tile's global
    batch/column offsets. Counters address the LOGICAL tensor (`b_total` =
    unpadded batch, `n_total` = padded column count), so any block shape —
    and the bulk oracle below — reads the same draws; batch-padding rows
    beyond `b_total` alias other counters but are sliced away by the caller.
    """
    rows = row0 + lax.broadcasted_iota(jnp.uint32, (bb, bn), 0)
    cols = col0 + lax.broadcasted_iota(jnp.uint32, (bb, bn), 1)
    ctr = (_u32(k) * jnp.uint32(b_total) + rows) * jnp.uint32(n_total) + cols
    return gauss_from_counter(seed, ctr)


def read_noise_array(seed, kb: int, b: int, np_: int) -> jnp.ndarray:
    """The full `[KB, B, Np]` standard-normal tensor, counter-addressed.

    The oracle (`kernels/ref.py`) and the moment/parity tests materialize
    noise through this; the Pallas kernel never does."""
    ctr = lax.broadcasted_iota(jnp.uint32, (kb, b, np_), 0)
    ctr = ctr * jnp.uint32(b) + lax.broadcasted_iota(jnp.uint32, (kb, b, np_), 1)
    ctr = ctr * jnp.uint32(np_) + lax.broadcasted_iota(jnp.uint32, (kb, b, np_), 2)
    return gauss_from_counter(seed, ctr)
