"""Plain reference of the decoder served on the analog (AIMC) path.

A llama-style decoder (RMSNorm, rotary embedding over half-split heads,
grouped-query causal attention with optional q/k/v biases, SwiGLU MLP, a
tied or untied head) whose seven projections per layer run through the AIMC
crossbar as the configuration states it:

  programming  each [K, N] weight is cut into row blocks of ``tile_rows``;
               each (block, column) gets the scale max|w| / 127 and int8
               codes round(w / scale), clipped to +-127;
  one call     the input rows of one call share one DAC scale
               s_x = max|x| / 127 and are quantised to int8; each row block's
               int32 dot product is quantised by an 8-bit ADC of step
               adc_alpha * sqrt(tile_rows) * 127 accumulator units; the
               blocks' codes, times their column scales and the step and
               s_x, are summed, and the bias (if any) is added.

The embedding, attention, norms and the head are float32 at `highest`
precision. Nothing here imports the program or reads what it made: the
weights are drawn from the seed with the same draws the model's `init`
makes (a normal per matrix from the keys `split(key, 16)` gives, per layer
from a further split), the q/k/v biases with the draws the harness makes
(`BIAS_KEY`), and all of it is programmed here.

One call of the reference covers one row of ``tokens``: the rows of a
sequence that ``valid`` marks share its DAC scale. The harness scores two
layouts: a request's prompt and served tokens as one sequence, and a
request's prompt right-padded with token 0 to the engine's prompt length
with every row valid -- the rows the engine's prefill quantises together.

A control computes the same in a lower precision: ``codes`` 7 rounds each
weight to int4 levels of its block and column before programming (int4
below the configured int8); ``dtype`` "bfloat16" computes every float32
part -- the residual stream, norms, rotary, attention, head, the crossbar's
dequantised output -- in bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# index of each matrix's key in split(key, 16), as the model draws them
KEY_INDEX = {"embed": 0, "wq": 1, "wk": 2, "wv": 3, "wo": 4,
             "w_gate": 9, "w_up": 10, "w_down": 11, "unembed": 12}
# the q/k/v biases: normals of std `bias_std` from split(fold_in(key, 3), 3)
BIAS_KEY = 3
HIGHEST = jax.lax.Precision.HIGHEST


def _pad(v: int, m: int) -> int:
    return -(-v // m) * m


def normal_matrix(key, k: int, n: int):
    return jax.random.normal(key, (k, n), jnp.float32) * (2.0 / (k + n)) ** 0.5


def qkv_biases(key, n_layers: int, widths, std: float):
    """[L, n] biases for q, k and v, as the harness draws them."""
    ks = jax.random.split(jax.random.fold_in(key, BIAS_KEY), 3)
    return [jax.random.normal(k, (n_layers, n), jnp.float32) * std
            for k, n in zip(ks, widths)]


def program(w, tile_rows: int, codes: int = 127):
    """[K, N] float32 -> (int8 codes [KB, tile_rows, Np], scales [KB, Np])."""
    k, n = w.shape
    kb, np_ = _pad(k, tile_rows) // tile_rows, _pad(n, 128)
    wb = jnp.zeros((kb * tile_rows, np_), jnp.float32).at[:k, :n].set(w)
    wb = wb.reshape(kb, tile_rows, np_)
    amax = jnp.max(jnp.abs(wb), axis=1, keepdims=True)
    if codes != 127:
        s = jnp.maximum(amax, 1e-12) * (1.0 / codes)
        wb = jnp.clip(jnp.round(wb / s), -codes, codes) * s
        amax = jnp.max(jnp.abs(wb), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) * (1.0 / 127)
    q = jnp.clip(jnp.round(wb / scale), -127, 127).astype(jnp.int8)
    return q, scale[:, 0, :]


def crossbar(x, valid, q, scale, n: int, adc_step: float):
    """One call per row of x: x [R, T, K], valid [R, T] -> [R, T, n] f32."""
    x = x.astype(jnp.float32)
    r, t, k = x.shape
    kb, m, np_ = q.shape
    amax = jnp.max(jnp.where(valid[..., None], jnp.abs(x), 0.0), axis=(1, 2))
    s_x = jnp.maximum(amax, 1e-12) * (1.0 / 127)                    # [R]
    xq = jnp.clip(jnp.round(x / s_x[:, None, None]), -127, 127)
    xq = jnp.pad(xq, ((0, 0), (0, 0), (0, kb * m - k))).astype(jnp.int8)
    xq = xq.reshape(r * t, kb, m)
    y = jnp.zeros((r * t, np_), jnp.float32)
    for b in range(kb):
        acc = jax.lax.dot(xq[:, b], q[b], preferred_element_type=jnp.int32)
        adc = jnp.clip(jnp.round(acc.astype(jnp.float32) / adc_step),
                       -127, 127)
        y = y + adc * scale[b]
    y = y.reshape(r, t, np_)[..., :n]
    return y * (adc_step * s_x)[:, None, None]


def rmsnorm(x, eps, dt):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            ).astype(dt)


def rotary(x, pos, theta):
    """x [R, T, H, D]: the first and second halves of each head rotate as
    pairs at frequencies theta ** (-i / (D / 2))."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _dot_kw(dt):
    """float32 at `highest`; bfloat16 operands accumulate in float32."""
    if dt == jnp.float32:
        return {"precision": HIGHEST}
    return {"preferred_element_type": jnp.float32}


def attention(q, k, v, valid):
    """Causal grouped-query attention: q [R, T, Hq, D], k/v [R, T, Hkv, D],
    in the dtype of q."""
    dt = q.dtype
    r, t, hq, d = q.shape
    g = hq // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k, **_dot_kw(dt)) / d ** 0.5
    mask = jnp.tril(jnp.ones((t, t), bool))[None, None] & valid[:, None,
                                                                None, :]
    s = jnp.where(mask, s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    return jnp.einsum("rhqk,rkhd->rqhd", p, v, **_dot_kw(dt)).astype(dt)


@functools.partial(jax.jit, static_argnames=("dims", "codes", "dtype"))
def layer(h, valid, keys, biases, dims: tuple, codes: int, dtype: str):
    """One decoder layer; its weights are drawn from ``keys`` (one per
    matrix, in the order wq wk wv wo w_gate w_up w_down) and programmed.
    ``biases``: the layer's q/k/v biases, or None."""
    d, hq, hkv, hd, ff, theta, eps, tile_rows, adc_step = dims
    dt = jnp.dtype(dtype)
    r, t, _ = h.shape
    shapes = [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d),
              (d, ff), (d, ff), (ff, d)]

    def proj(x, i, bias=None):
        k, n = shapes[i]
        q, s = program(normal_matrix(keys[i], k, n), tile_rows, codes)
        y = crossbar(x, valid, q, s, n, adc_step)
        return (y if bias is None else y + bias).astype(dt)

    bq, bk, bv = biases if biases is not None else (None,) * 3
    pos = jnp.broadcast_to(jnp.arange(t), (r, t))
    a = rmsnorm(h, eps, dt)
    q = proj(a, 0, bq).reshape(r, t, hq, hd)
    k = proj(a, 1, bk).reshape(r, t, hkv, hd)
    v = proj(a, 2, bv).reshape(r, t, hkv, hd)
    att = attention(rotary(q, pos, theta), rotary(k, pos, theta), v, valid)
    h = (h + proj(att.reshape(r, t, hq * hd), 3)).astype(dt)
    a = rmsnorm(h, eps, dt)
    f = (jax.nn.silu(proj(a, 4).astype(jnp.float32))
         * proj(a, 5).astype(jnp.float32)).astype(dt)
    return (h + proj(f, 6)).astype(dt)


@functools.partial(jax.jit, static_argnames=("v", "d", "dtype"))
def _embed(key, tokens, v: int, d: int, dtype: str):
    table = jax.random.normal(key, (v, d), jnp.float32) * 0.02
    return jnp.take(table, tokens, axis=0).astype(dtype)


@functools.partial(jax.jit,
                   static_argnames=("d", "v", "eps", "tied", "dtype"))
def _score(key, h, cands, d: int, v: int, eps: float, tied: bool,
           dtype: str):
    """Per position: the head's best logit, the logits of the candidate
    tokens ``cands`` [C, R, T], and the argmax. h [R, T, d]. A tied head is
    the embedding table (drawn from ``key`` as `_embed` draws it), an untied
    one its own matrix."""
    dt = jnp.dtype(dtype)
    if tied:
        head = (jax.random.normal(key, (v, d), jnp.float32) * 0.02).T
    else:
        head = normal_matrix(key, d, v)
    head = head.astype(dt)
    h = rmsnorm(h, eps, dt)

    def row(args):
        hr, cr = args                                   # [T, d], [C, T]
        logits = jnp.dot(hr, head, **_dot_kw(dt)).astype(jnp.float32)
        pick = logits[jnp.arange(logits.shape[0])[None], cr]
        return (jnp.max(logits, -1), pick,
                jnp.argmax(logits, -1).astype(jnp.int32))

    return jax.lax.map(row, (h, jnp.moveaxis(cands, 1, 0)))


def forward_scores(dims: dict, key, tokens, valid, cands, codes: int = 127,
                   dtype: str = "float32"):
    """Run the reference over ``tokens`` [R, T] (``valid`` marks the rows of
    each sequence) and score every position: returns (best logit [R, T],
    logits of ``cands`` [C, R, T] at each position, argmax [R, T]).

    ``dims``: n_layers, d_model, n_heads, n_kv_heads, head_dim, d_ff, vocab,
    rope_theta, norm_eps, qkv_bias, bias_std, tie_embeddings, tile_rows,
    adc_alpha."""
    ks = jax.random.split(key, 16)
    names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    n_layers, d, v = dims["n_layers"], dims["d_model"], dims["vocab"]
    hq, hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    per_layer = jnp.stack([jax.random.split(ks[KEY_INDEX[n]], n_layers)
                           for n in names], axis=1)       # [L, 7, key]
    biases = (qkv_biases(key, n_layers, (hq * hd, hkv * hd, hkv * hd),
                         dims["bias_std"]) if dims["qkv_bias"] else None)
    tile_rows = int(dims["tile_rows"])
    adc_step = float(max(1.0, dims["adc_alpha"] * tile_rows ** 0.5 * 127))
    h = _embed(ks[KEY_INDEX["embed"]], tokens, v, d, dtype)
    static = (d, hq, hkv, hd, dims["d_ff"], float(dims["rope_theta"]),
              float(dims["norm_eps"]), tile_rows, adc_step)
    for i in range(n_layers):
        b = None if biases is None else tuple(x[i] for x in biases)
        h = layer(h, valid, per_layer[i], b, static, codes, dtype)
    tied = bool(dims["tie_embeddings"])
    head_key = ks[KEY_INDEX["embed" if tied else "unembed"]]
    best, picked, top = _score(head_key, h, cands, d, v,
                               float(dims["norm_eps"]), tied, dtype)
    return best, jnp.moveaxis(picked, 0, 1), top
