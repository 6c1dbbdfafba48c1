"""Plain reference of Mellum2-12B-A2.5B served on the analog (AIMC) path.

The decoder, layer by layer:

  attention    RMSNorm; q/k/v projections (no bias, no QK norm); rotary
               embedding over half-split heads at the layer kind's table;
               grouped-query causal attention, which on a sliding layer
               sees only the last ``sliding_window`` positions (a key at
               position j is seen from position i when i - W < j <= i);
               the output projection; the residual add.
  rope         sliding layers: the default table, inverse frequencies
               theta ** (-2i / D). Full layers: YaRN -- each frequency a
               blend of theta ** (-2i / D) and the same over ``factor``,
               by a ramp over the dims between the wavelength counts
               ``beta_fast`` and ``beta_slow`` fit in the original context
               (bounds floored and ceiled to whole dims), and cos and sin
               scaled by ``attention_factor``.
  experts      RMSNorm; a float32 router [d, E], softmax over the E
               experts, the top k, their gates renormalised to sum to 1;
               every token goes through each of its k experts -- a SwiGLU
               FFN of width d_ff, down(silu(gate(x)) * up(x)) -- and the
               gate-weighted outputs are summed onto the residual.
  head         RMSNorm and an untied [d, vocab] float32 head.

Every projection of attention and of the experts runs through the
crossbar as the configuration states it:

  programming  each [K, N] weight is cut into row blocks of ``tile_rows``;
               each (block, column) gets the scale max|w| / 127 and int8
               codes round(w / scale), clipped to +-127;
  one call     the input rows of one call share one DAC scale
               s_x = max|x| / 127 and are quantised to int8; each row
               block's int32 dot product is quantised by an 8-bit ADC of
               step adc_alpha * sqrt(tile_rows) * 127 accumulator units;
               the blocks' codes, times their column scales and the step
               and s_x, are summed.

One call covers one row of ``tokens`` (the rows of a sequence that
``valid`` marks); for an expert, the rows of that sequence routed to it.
The router, attention, norms, embedding and head are float32 at `highest`.

Nothing here imports the program or reads what it made. The weights are
drawn from the seed with the draws the model's `init` makes: from the keys
`split(key, 16)` gives, the embedding (0), q k v o (1-4), the router (5),
the experts' gate, up and down (6-8) and the head (12); per layer from a
further `split(., n_layers)`, per expert from `split(., n_experts)`.

Departures from the published model: no QK norm (the config has none), the
multi-token-prediction head is not served, and weights are random. The
published constants below (experts, window, layer pattern, rope) can be
overridden by keyword, for the tests at a small size on the CPU.

Controls, one precision below the configured one: ``codes`` 7 rounds each
weight to int4 levels of its block and column before programming;
``dtype`` "bfloat16" computes every float32 part -- residual stream, norms,
router, rotary, attention, expert activations, head -- in bfloat16;
``sliding_window`` 0 drops the sliding layers' window (every layer then
attends to the whole causal context).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# index of each matrix's key in split(key, 16), as the model draws them
KEY_INDEX = {"embed": 0, "wq": 1, "wk": 2, "wv": 3, "wo": 4, "router": 5,
             "we_gate": 6, "we_up": 7, "we_down": 8, "unembed": 12}
HIGHEST = jax.lax.Precision.HIGHEST

# the published constants (config.json)
N_EXPERTS = 64
TOP_K = 8
SLIDING_WINDOW = 1024
LAYER_TYPES = ("sliding_attention",) * 3 + ("full_attention",)
ROPE = {
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000.0},
    "full_attention": {"rope_type": "yarn", "rope_theta": 500000.0,
                       "factor": 16.0,
                       "original_max_position_embeddings": 8192,
                       "beta_fast": 32.0, "beta_slow": 1.0,
                       "attention_factor": 1.2772588722239782},
}


def _pad(v: int, m: int) -> int:
    return -(-v // m) * m


def normal_matrix(key, k: int, n: int):
    return jax.random.normal(key, (k, n), jnp.float32) * (2.0 / (k + n)) ** 0.5


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


def crossbar(x, rows, q, scale, n: int, adc_step: float, dac: float):
    """One call per row of x over the rows ``rows`` marks: x [R, T, K],
    rows [R, T] -> [R, T, n] float32 (unmarked rows are computed under the
    marked rows' scale and are the caller's to drop). ``dac`` > 0 fixes the
    DAC scale."""
    x = x.astype(jnp.float32)
    r, t, k = x.shape
    kb, m, np_ = q.shape
    if dac > 0:
        s_x = jnp.full((r,), dac, jnp.float32)
    else:
        amax = jnp.max(jnp.where(rows[..., None], jnp.abs(x), 0.0),
                       axis=(1, 2))
        s_x = jnp.maximum(amax, 1e-12) * (1.0 / 127)                # [R]
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


def rope_table(head_dim: int, section: dict) -> tuple[np.ndarray, float]:
    """(inverse frequencies [head_dim / 2], cos/sin scale) of one rope
    section, written out from the published formulas."""
    base = float(section["rope_theta"])
    i = np.arange(0, head_dim, 2, dtype=np.float64)
    plain = 1.0 / base ** (i / head_dim)
    if section["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    factor = float(section["factor"])
    ctx = float(section["original_max_position_embeddings"])

    def dim(turns):
        return (head_dim * math.log(ctx / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    lo = max(math.floor(dim(float(section["beta_fast"]))), 0)
    hi = min(math.ceil(dim(float(section["beta_slow"]))), head_dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    inv = (plain / factor) * ramp + plain * (1.0 - ramp)
    return inv.astype(np.float32), float(section["attention_factor"])


def rotary(x, pos, inv_freq, scale):
    """x [R, T, H, D]: the first and second halves of each head rotate as
    pairs at ``inv_freq``; cos and sin times ``scale``."""
    half = x.shape[-1] // 2
    ang = pos[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(ang) * scale)[:, :, None]
    sin = (jnp.sin(ang) * scale)[:, :, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _dot_kw(dt):
    """float32 at `highest`; bfloat16 operands accumulate in float32."""
    if dt == jnp.float32:
        return {"precision": HIGHEST}
    return {"preferred_element_type": jnp.float32}


def attention(q, k, v, valid, window, q_block: int = 512):
    """Causal grouped-query attention, over the last ``window`` positions
    where ``window`` (an int32 scalar) is > 0: q [R, T, Hq, D], k/v
    [R, T, Hkv, D], in the dtype of q. Computed a block of queries at a
    time."""
    dt = q.dtype
    r, t, hq, d = q.shape
    g = hq // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    qb = min(q_block, t)
    n_blocks = -(-t // qb)
    qp = jnp.pad(q, ((0, 0), (0, n_blocks * qb - t), (0, 0), (0, 0)))
    kpos = jnp.arange(t)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qp, i * qb, qb, axis=1)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.einsum("rqhd,rkhd->rhqk", qi, k, **_dot_kw(dt)) / d ** 0.5
        mask = kpos[None] <= qpos[:, None]
        mask &= (window <= 0) | (kpos[None] > qpos[:, None] - window)
        mask = mask[None, None] & valid[:, None, None, :]
        s = jnp.where(mask, s.astype(jnp.float32), -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        return jnp.einsum("rhqk,rkhd->rqhd", p, v, **_dot_kw(dt)).astype(dt)

    out = jax.lax.map(block, jnp.arange(n_blocks))      # [nb, R, qb, H, D]
    out = jnp.moveaxis(out, 0, 1).reshape(r, n_blocks * qb, hq, d)
    return out[:, :t]


@functools.partial(jax.jit, static_argnames=("dims", "codes", "dtype"))
def layer(h, valid, keys, inv_freq, rope_scale, window, dims: tuple,
          codes: int, dtype: str):
    """One decoder layer; its weights are drawn from ``keys`` (wq, wk, wv,
    wo, router, we_gate, we_up, we_down: this layer's key of each) and
    programmed. ``window`` 0: full attention."""
    (d, hq, hkv, hd, ff, e, top_k, eps, tile_rows, adc_step, dac) = dims
    dt = jnp.dtype(dtype)
    r, t, _ = h.shape
    shapes = [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d)]

    def proj(x, w, n, rows=valid):
        q, s = program(w, tile_rows, codes)
        return crossbar(x, rows, q, s, n, adc_step, dac)

    pos = jnp.broadcast_to(jnp.arange(t), (r, t))
    a = rmsnorm(h, eps, dt)
    qkv = [proj(a, normal_matrix(keys[i], kk, n), n).astype(dt)
           for i, (kk, n) in enumerate(shapes[:3])]
    q = rotary(qkv[0].reshape(r, t, hq, hd), pos, inv_freq, rope_scale)
    k = rotary(qkv[1].reshape(r, t, hkv, hd), pos, inv_freq, rope_scale)
    v = qkv[2].reshape(r, t, hkv, hd)
    att = attention(q, k, v, valid, window)
    h = (h + proj(att.reshape(r, t, hq * hd),
                  normal_matrix(keys[3], hq * hd, d), d)).astype(dt)

    a = rmsnorm(h, eps, dt)
    logits = jnp.dot(a, normal_matrix(keys[4], d, e).astype(dt),
                     **_dot_kw(dt)).astype(dt)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(dt)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # each token's gate on every expert: 0 where the expert is not its pick
    gates = jnp.sum(jnp.where(top_i[..., None] == jnp.arange(e),
                              top_p[..., None], 0.0), axis=-2)   # [R, T, E]
    expert_keys = jnp.stack([jax.random.split(keys[5 + i], e)
                             for i in range(3)], axis=1)        # [E, 3, key]

    def expert(acc, xs):
        ks, gate = xs                                   # [3, key], [R, T]
        routed = valid & (gate > 0)
        g = proj(a, normal_matrix(ks[0], d, ff), ff, routed)
        u = proj(a, normal_matrix(ks[1], d, ff), ff, routed)
        f = (jax.nn.silu(g.astype(dt).astype(jnp.float32))
             * u.astype(dt).astype(jnp.float32)).astype(dt)
        y = proj(f, normal_matrix(ks[2], ff, d), d, routed).astype(dt)
        return acc + gate[..., None].astype(jnp.float32) * y, None

    moe, _ = jax.lax.scan(expert, jnp.zeros((r, t, d), jnp.float32),
                          (expert_keys, jnp.moveaxis(gates, -1, 0)))
    return (h + moe.astype(dt)).astype(dt)


@functools.partial(jax.jit, static_argnames=("v", "d", "dtype"))
def _embed(key, tokens, v: int, d: int, dtype: str):
    table = jax.random.normal(key, (v, d), jnp.float32) * 0.02
    return jnp.take(table, tokens, axis=0).astype(dtype)


@functools.partial(jax.jit, static_argnames=("d", "v", "eps", "dtype"))
def _score(key, h, cands, d: int, v: int, eps: float, dtype: str):
    """Per position: the untied head's best logit, the logits of the
    candidate tokens ``cands`` [C, R, T], and the argmax. h [R, T, d]."""
    dt = jnp.dtype(dtype)
    head = normal_matrix(key, d, v).astype(dt)
    h = rmsnorm(h, eps, dt)

    def row(args):
        hr, cr = args                                   # [T, d], [C, T]
        logits = jnp.dot(hr, head, **_dot_kw(dt)).astype(jnp.float32)
        pick = logits[jnp.arange(logits.shape[0])[None], cr]
        return (jnp.max(logits, -1), pick,
                jnp.argmax(logits, -1).astype(jnp.int32))

    return jax.lax.map(row, (h, jnp.moveaxis(cands, 1, 0)))


def forward_scores(dims: dict, key, tokens, valid, cands, codes: int = 127,
                   dtype: str = "float32", *,
                   sliding_window: int = SLIDING_WINDOW,
                   n_experts: int = N_EXPERTS, top_k: int = TOP_K,
                   layer_types=LAYER_TYPES, rope=None,
                   dac_scale: float = 0.0):
    """Run the reference over ``tokens`` [R, T] (``valid`` marks the rows of
    each sequence) and score every position: returns (best logit [R, T],
    logits of ``cands`` [C, R, T] at each position, argmax [R, T]).

    ``dims``: n_layers, d_model, n_heads, n_kv_heads, head_dim, d_ff (the
    expert width), vocab, norm_eps, tile_rows, adc_alpha. Layer i is of
    kind ``layer_types[i % len(layer_types)]``. ``dac_scale`` > 0 fixes
    every call's DAC scale (the program's ``input_scale``)."""
    rope = ROPE if rope is None else rope
    ks = jax.random.split(key, 16)
    names = ("wq", "wk", "wv", "wo", "router", "we_gate", "we_up", "we_down")
    n_layers, d, v = dims["n_layers"], dims["d_model"], dims["vocab"]
    hq, hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    per_layer = jnp.stack([jax.random.split(ks[KEY_INDEX[n]], n_layers)
                           for n in names], axis=1)       # [L, 8, key]
    tile_rows = int(dims["tile_rows"])
    adc_step = float(max(1.0, dims["adc_alpha"] * tile_rows ** 0.5 * 127))
    eps = float(dims["norm_eps"])
    static = (d, hq, hkv, hd, dims["d_ff"], n_experts, top_k, eps, tile_rows,
              adc_step, float(dac_scale))
    h = _embed(ks[KEY_INDEX["embed"]], tokens, v, d, dtype)
    for i in range(n_layers):
        kind = layer_types[i % len(layer_types)]
        inv_freq, scale = rope_table(hd, rope[kind])
        window = sliding_window if kind == "sliding_attention" else 0
        h = layer(h, valid, per_layer[i], jnp.asarray(inv_freq),
                  jnp.float32(scale), jnp.int32(window), static, codes, dtype)
    best, picked, top = _score(ks[KEY_INDEX["unembed"]], h, cands, d, v, eps,
                               dtype)
    return best, jnp.moveaxis(picked, 0, 1), top
