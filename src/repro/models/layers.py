"""Shared model-zoo layers, all AIMC-capable.

Every stationary-weight projection in the zoo routes through `linear()`,
which executes one of three ways:

  * digital         — plain matmul (the paper's CPU+SIMD baseline);
  * AIMC, programmed — the weight arrives as a pre-programmed
    `AimcLinearState` (installed by `core.program.AimcProgram.install`):
    apply-only queue/process/dequeue, NO re-programming on the hot path.
    This is the paper's deployment model (weights stationary in crossbars)
    and the serving configuration;
  * AIMC, on-the-fly — `core.aimc.aimc_linear_ste` re-programs with a fresh
    noise draw every call and backprops straight-through (noise-aware
    training).

Attention uses a chunked online-softmax implementation (flash attention as a
pure-JAX double scan) so both 4k training and 32k prefill are O(seq) in
memory. GQA-aware; supports causal and sliding-window masks. Attention
score.V / QK^T products are *never* AIMC-mapped: both operands are
activations (see DESIGN.md §4 applicability boundary).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aimc import (AimcConfig, AimcLinearState, aimc_apply,
                             aimc_apply_stacked, aimc_linear_ste)
from repro.kernels.ref import EPILOGUE_FNS
from repro.tracing import AIMC_MVM


# ---------------------------------------------------------------------------
# Execution context: how linears run, threaded through every model.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Execution:
    """Static execution choices (hashable; safe as a jit static arg)."""
    mode: str = "digital"                  # digital | aimc
    aimc: AimcConfig = AimcConfig()
    compute_dtype: str = "bfloat16"
    # int8-native serving path (beyond-paper §Perf optimization): weights are
    # stored/streamed as int8 codes and dequantized in the MXU epilogue.
    serve_int8: bool = False
    # program-once/apply-many handle (core.program): True declares that an
    # AimcProgram has been install()ed into the parameter tree. Mapped
    # projections arrive at `linear` as AimcLinearState and run apply-only;
    # raw weights that remain (plan-excluded projections) stay DIGITAL
    # instead of silently re-programming per call — re-programming on the
    # hot path is exactly what the program API removes. `aimc` must be the
    # same AimcConfig the program was built with (ADC step/noise agreement).
    programmed: bool = False

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def matmul_precision(self):
        """Context for tracing model code at the stated compute dtype.

        A TPU runs a float32 matmul at DEFAULT precision as one bf16 MXU
        pass, or, with one row, as an f32 multiply-reduce, so float32
        execution pins ``highest``. Narrower dtypes keep the platform
        default. The CPU computes float32 matmuls in float32 either way."""
        if self.cdtype == jnp.float32:
            return jax.default_matmul_precision("highest")
        return contextlib.nullcontext()


DIGITAL = Execution()


def at_stated_precision(fn, exe: Execution):
    """`fn` traced under ``exe.matmul_precision()``: wrap what gets jitted."""
    @functools.wraps(fn)
    def traced(*args, **kw):
        with exe.matmul_precision():
            return fn(*args, **kw)
    return traced


# ---------------------------------------------------------------------------
# Activation sharding hints (§Perf iteration 1).
#
# Without explicit constraints GSPMD re-shards activations between every
# scanned layer (measured: 300-850 s collective terms on the 16x16 mesh).
# `shard_act` pins batch to the data axes and, when the dimension divides,
# one feature dimension (heads / d_ff / experts / vocab) to `model`. Applied
# only when a concrete mesh is active, so plain CPU tests are unaffected.
# ---------------------------------------------------------------------------

def _current_mesh():
    from repro.compat import current_mesh
    return current_mesh()                  # works inside and outside jit


def shard_act(x: jnp.ndarray, model_dim: int | None = None):
    """Constrain activation x: dim0 -> data axes, model_dim -> 'model'."""
    import os
    from jax.sharding import PartitionSpec as P
    if os.environ.get("REPRO_NO_ACTSHARD"):   # baseline reproduction switch
        return x
    mesh = _current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return x
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    spec = [None] * x.ndim
    if x.shape[0] % dp_n == 0 and dp_n > 1:
        spec[0] = dp
    if (model_dim is not None
            and x.shape[model_dim] % mesh.shape["model"] == 0):
        spec[model_dim] = "model"
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def mask_batch_select(new, old, active, axis: int = 0):
    """Per-request freeze: keep `new` where `active`, else `old`.

    `active`: [B] bool; `axis` is the batch axis of the (same-shape) arrays.
    The freeze for O(1)-per-slot recurrent state: the continuous-batching
    engine selects a retired/empty slot's old state through a decode step,
    and `recurrent_prefill` through a prompt's padding. A transformer's KV
    cache is not frozen this way — a select reads and writes the whole
    cache every step; `transformer.decode_step(active=...)` drops the
    frozen lanes' rows instead."""
    shape = [1] * new.ndim
    shape[axis] = active.shape[0]
    return jnp.where(active.reshape(shape), new, old)


def recurrent_prefill(decode_fn, cache0, tokens, n_vocab, valid_len=None):
    """Serving prefill for O(1)-state archs (xlstm / rglru): scan the
    single-token decode recurrence over a (right-padded) prompt batch.

    ``decode_fn(cache, tok[B,1]) -> (logits [B,1,V], new_cache)`` is the
    model's own decode step with params/cfg closed over; the cache is a flat
    dict whose leaves carry batch at axis 1 except ``"len"`` (axis 0) — the
    shared recurrent-cache layout. Steps at positions >= ``valid_len`` are
    padding: the cache is bit-frozen through them (mask_batch_select), and
    the returned logits are each row's own last valid step. One fixed
    padded shape serves every ragged prompt (jit-stability contract)."""
    b, s = tokens.shape
    vl = (jnp.full((b,), s, jnp.int32) if valid_len is None
          else valid_len.astype(jnp.int32))

    def step(carry, xs):
        cache, last = carry
        t, tok_t = xs
        logits, new_cache = decode_fn(cache, tok_t[:, None])
        active = t < vl
        cache = {k: mask_batch_select(new_cache[k], cache[k], active,
                                      axis=0 if k == "len" else 1)
                 for k in new_cache}
        last = jnp.where((t == vl - 1)[:, None, None],
                         logits.astype(jnp.float32), last)
        return (cache, last), None

    last0 = jnp.zeros((b, 1, n_vocab), jnp.float32)
    (cache, logits), _ = jax.lax.scan(step, (cache0, last0),
                                      (jnp.arange(s), tokens.T))
    return logits, cache


def as_weight(w, dtype):
    """Materialize a weight that may be stored as int8 codes + scales.

    The paper's number format as a serving optimization (§Perf): weights live
    in HBM as int8 (half the bytes of bf16, quarter of f32) and dequantize in
    VMEM right before the MXU — the digital shadow of keeping them stationary
    in a crossbar."""
    if isinstance(w, dict) and "q" in w:
        return w["q"].astype(dtype) * w["s"].astype(dtype)
    return w.astype(dtype)


def linear(x: jnp.ndarray, w: jnp.ndarray, exe: Execution,
           key: jax.Array | None = None, bias: jnp.ndarray | None = None,
           activation: str = "none"):
    """The AIMC-or-digital projection. x: [..., K], w: [K, N] — or a
    pre-programmed `AimcLinearState` (program-once/apply-many serving).

    `bias`/`activation` are the layer epilogue: on the programmed AIMC path
    they fuse into the kernel's last row-block step (kernel v2, no separate
    XLA op); elsewhere they run as the equivalent post-ops."""
    if isinstance(w, AimcLinearState):
        # programmed crossbar tenant: apply-only, CM_INITIALIZE already paid;
        # the epilogue rides the kernel (cfg.fuse_epilogue) in f32.
        if exe.aimc is None:
            raise ValueError(
                "programmed AimcLinearState reached linear() but exe.aimc "
                "is None — install()ed params require an Execution carrying "
                "the AimcConfig the program was built with")
        with jax.named_scope(AIMC_MVM):
            y = aimc_apply(w, x, exe.aimc, key, bias=bias,
                           activation=activation)
        return y.astype(exe.cdtype)
    if exe.mode == "aimc" and not exe.programmed:
        y = aimc_linear_ste(x, as_weight(w, jnp.float32), key, exe.aimc)
        y = y.astype(exe.cdtype)
    else:
        y = x.astype(exe.cdtype) @ as_weight(w, exe.cdtype)
    if bias is not None:
        y = y + bias.astype(exe.cdtype)
    return EPILOGUE_FNS[activation](y)


def linear_stack(x: jnp.ndarray, ws, exe: Execution,
                 key: jax.Array | None = None, biases=None,
                 activations="none"):
    """Gate-fused multi-MVM projection: G same-shape matrices sharing one
    input (LSTM gates, attention QKV, gate/up FFN pairs) -> tuple of G
    outputs.

    `ws` is either a `[G, ...]`-stacked programmed `AimcLinearState` (built
    once at install time by a model's `fuse_gate_stacks`) — executed as ONE
    weight-stationary kernel launch sharing the input block and DAC scale —
    or a sequence of per-gate weights, which falls back to per-gate
    `linear()` calls (bit-equal noise-off)."""
    if isinstance(ws, AimcLinearState):
        g = ws.stack_shape[-1]
        with jax.named_scope(AIMC_MVM):
            y = aimc_apply_stacked(ws, x, exe.aimc, key, biases=biases,
                                   activations=activations)
        y = y.astype(exe.cdtype)
        return tuple(y[i] for i in range(g))
    g = len(ws)
    if isinstance(activations, str):
        activations = (activations,) * g
    if biases is None:
        biases = (None,) * g
    keys = jax.random.split(key, g) if key is not None else (None,) * g
    return tuple(linear(x, w, exe, k_, bias=b, activation=a)
                 for w, k_, b, a in zip(ws, keys, biases, activations))


# ---------------------------------------------------------------------------
# Norms / embeddings / positional encodings
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
         inv_freq=None, scale=None):
    """Rotary embedding. x: [B, S, H, D] (D even), positions: [B, S].

    ``inv_freq`` ([D/2], a layer's table from `rope_params`) replaces the
    default frequencies ``theta ** (-i / (D/2))``; ``scale`` multiplies cos
    and sin (YaRN's attention factor)."""
    d = x.shape[-1]
    half = d // 2
    freqs = (1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
             if inv_freq is None else inv_freq)
    angles = positions[..., None].astype(jnp.float32) * freqs      # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def rope_params(head_dim: int, params: dict) -> tuple[np.ndarray, float]:
    """(inverse frequencies [head_dim/2], cos/sin scale) of one rope
    section as a HuggingFace ``rope_parameters`` entry states it:
    ``rope_type`` "default" (``rope_theta``) or "yarn" (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``; YaRN's ramp between interpolated and extrapolated
    frequencies, correction range truncated to whole dims)."""
    base = float(params["rope_theta"])
    pos = base ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    kind = params.get("rope_type", "default")
    if kind == "default":
        return (1.0 / pos).astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"unknown rope_type {kind!r}")
    factor = float(params["factor"])
    orig = float(params["original_max_position_embeddings"])

    def dim_of(rot):        # the dim whose wavelength fits `rot` turns
        return (head_dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_of(float(params.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim_of(float(params.get("beta_slow", 1)))),
               head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    extra = 1.0 - ramp               # share of the extrapolated frequency
    inv = (1.0 / (factor * pos)) * (1.0 - extra) + (1.0 / pos) * extra
    scale = params.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(scale)


# ---------------------------------------------------------------------------
# Flash attention: chunked double-scan online softmax, GQA-aware.
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _attn_chunk(q, k, v, q_pos, kv_pos, carry, scale, causal, window, kv_valid):
    """One (q-chunk x kv-chunk) online-softmax update.

    q: [B, G*Hkv, qc, D] grouped-query layout; k/v: [B, Hkv, kc, D].
    carry = (m [B,Hq,qc], l [B,Hq,qc], acc [B,Hq,qc,D]).
    """
    m, l, acc = carry
    b, hq, qc, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, qc, d)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale                 # [B,Hkv,G,qc,kc]
    mask = (kv_pos[None, :] < kv_valid)                           # pad mask
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= kv_pos[None, :] > (q_pos[:, None] - window)
    mask = jnp.broadcast_to(mask, (qc, k.shape[2]))
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    s = s.reshape(b, hq, qc, -1)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # explicit re-mask: a fully-masked chunk would otherwise yield
    # exp(NEG_INF - NEG_INF) = 1 and corrupt the accumulation
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask.reshape(1, 1, qc, -1), p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p.reshape(b, hkv, g * qc, -1),
                    v.astype(jnp.float32)).reshape(b, hq, qc, d)
    acc_new = acc * corr[..., None] + pv
    return m_new, l_new, acc_new


def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    q_chunk=1024, kv_chunk=1024, out_dtype=None):
    """q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D] -> [B, Sq, Hq, D].

    O(Sq/qc * Skv/kc) chunk pairs; memory O(qc*kc). The inner body is
    checkpointed so the backward pass recomputes scores (flash-style).
    """
    b, sq0, hq, d = q.shape
    _, skv0, hkv, _ = k.shape
    qc = min(q_chunk, sq0)
    kc = min(kv_chunk, skv0)
    # pad ragged sequence lengths up to a whole number of chunks; padded KV
    # positions are masked out, padded Q rows are sliced off at the end
    sq = -(-sq0 // qc) * qc
    skv = -(-skv0 // kc) * kc
    if sq != sq0:
        q = jnp.pad(q, ((0, 0), (0, sq - sq0), (0, 0), (0, 0)))
    if skv != skv0:
        k = jnp.pad(k, ((0, 0), (0, skv - skv0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv - skv0), (0, 0), (0, 0)))
    scale = 1.0 / (d ** 0.5)
    out_dtype = out_dtype or q.dtype

    qh = jnp.moveaxis(q, 2, 1)                    # [B, Hq, Sq, D]
    kh = jnp.moveaxis(k, 2, 1)
    vh = jnp.moveaxis(v, 2, 1)
    q_blocks = qh.reshape(b, hq, sq // qc, qc, d).transpose(2, 0, 1, 3, 4)
    k_blocks = kh.reshape(b, hkv, skv // kc, kc, d).transpose(2, 0, 1, 3, 4)
    v_blocks = vh.reshape(b, hkv, skv // kc, kc, d).transpose(2, 0, 1, 3, 4)

    @jax.checkpoint
    def kv_body(carry, xs):
        kb, vb, j = xs
        q_blk, qi = carry[3], carry[4]
        q_pos = q_offset + qi * qc + jnp.arange(qc)
        kv_pos = j * kc + jnp.arange(kc)
        m, l, acc = _attn_chunk(q_blk, kb, vb, q_pos, kv_pos, carry[:3],
                                scale, causal, window, skv0)
        return (m, l, acc, q_blk, qi), None

    def q_body(_, xs):
        q_blk, qi = xs
        init = (jnp.full((b, hq, qc), NEG_INF, jnp.float32),
                jnp.zeros((b, hq, qc), jnp.float32),
                jnp.zeros((b, hq, qc, d), jnp.float32),
                q_blk, qi)
        (m, l, acc, _, _), _ = jax.lax.scan(
            kv_body, init, (k_blocks, v_blocks, jnp.arange(skv // kc)))
        o = acc / jnp.maximum(l, 1e-20)[..., None]
        return None, o.astype(out_dtype)

    _, o_blocks = jax.lax.scan(q_body, None,
                               (q_blocks, jnp.arange(sq // qc)))
    o = o_blocks.transpose(1, 2, 0, 3, 4).reshape(b, hq, sq, d)
    return jnp.moveaxis(o, 1, 2)[:, :sq0]


def decode_attention(q, k_cache, v_cache, kv_len=None, window=None):
    """Single-token attention against a KV cache (flash-decoding layout).

    q: [B, 1, Hq, D]; caches: [B, Skv, Hkv, D]; kv_len: [B] valid lengths.
    The cache's sequence axis is sharded over `model`; q stays REPLICATED
    over `model` (each shard computes partial attention over its sequence
    chunk) and the softmax/PV reductions psum only [B, H, G]-sized partials.
    The einsums contract directly against the [B, S, H, D] cache layout with
    ``preferred_element_type=f32`` — no transposed or f32-upcast copy of the
    cache is ever materialized (measured 20x HBM-traffic reduction on
    qwen15-110b decode_32k; EXPERIMENTS.md §Perf).
    """
    b, _, hq, d = q.shape
    _, skv, hkv, _ = k_cache.shape
    g = hq // hkv
    scale = 1.0 / (d ** 0.5)
    qg = shard_act(q.reshape(b, hkv, g, d))        # batch->dp, heads replicated
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(k_cache.dtype), k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(skv)
    if kv_len is not None:
        mask = pos[None] < kv_len[:, None]                        # [B, Skv]
        if window is not None:
            mask &= pos[None] > (kv_len[:, None] - 1 - window)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# FFN variants
# ---------------------------------------------------------------------------

def swiglu(x, w_gate, w_up, w_down, exe: Execution, key=None):
    k1, k2, k3 = _split3(key)
    g = shard_act(linear(x, w_gate, exe, k1), model_dim=x.ndim - 1)
    u = shard_act(linear(x, w_up, exe, k2), model_dim=x.ndim - 1)
    return linear(jax.nn.silu(g) * u, w_down, exe, k3)


def gelu_mlp(x, w_in, b_in, w_out, b_out, exe: Execution, key=None):
    k1, k2 = (None, None) if key is None else tuple(jax.random.split(key))
    h = jax.nn.gelu(linear(x, w_in, exe, k1, b_in))
    return linear(h, w_out, exe, k2, b_out)


def _split3(key):
    if key is None:
        return None, None, None
    return tuple(jax.random.split(key, 3))


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, k, n, dtype=jnp.float32):
    return (jax.random.normal(key, (k, n), dtype) * (2.0 / (k + n)) ** 0.5)


def embed_init(key, v, d, dtype=jnp.float32):
    return jax.random.normal(key, (v, d), dtype) * 0.02
