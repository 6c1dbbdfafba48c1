"""Decoder-only transformer family (granite, llama3.2, qwen1.5, glm4,
internvl2 backbone, arctic, olmoe).

One scanned, remat-wrapped block definition covers the dense and MoE members;
config flags select QKV bias (qwen), GQA group sizes, SwiGLU dims, MoE
(+ dense residual for arctic) and the VLM patch-embedding frontend stub
(internvl2: `input_specs` feeds precomputed patch embeddings; see the
assignment's frontend-STUB rule).

All stationary projections route through `layers.linear` and therefore run
digitally or through the simulated AIMC crossbars (the paper's technique as a
first-class execution mode). Serving uses program-once/apply-many: after
`core.program.program_model(...).install(params)`, the mapped projections
arrive here as stacked `AimcLinearState`s that `lax.scan` slices per layer —
no re-programming per token, no model-code changes. Parameters are stacked on
a leading layer axis and consumed by `lax.scan` — small HLO, fast multi-pod
compiles.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.aimc import AimcLinearState, stack_states
from repro.models import moe as moe_lib
from repro.models.layers import (Execution, as_weight, decode_attention,
                                 dense_init, embed_init, flash_attention,
                                 linear, linear_stack, rmsnorm, rope,
                                 rope_params, shard_act, swiglu)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_dense_residual: bool = False  # arctic: dense FFN in parallel with MoE
    moe_dense_ff: int = 0
    # VLM frontend stub
    n_patches: int = 0
    # attention chunking
    q_chunk: int = 1024
    kv_chunk: int = 1024
    # per-layer attention kinds, as HuggingFace's ``layer_types`` names them
    # ("sliding_attention" over the last ``sliding_window`` positions,
    # "full_attention"; the first n_layers entries apply), with one
    # ``rope_parameters`` section per kind (`layers.rope_params`). Empty:
    # every layer is full attention with the default rope at rope_theta.
    layer_types: tuple = ()
    sliding_window: int = 0
    rope_parameters: tuple = ()       # ((kind, ((key, value), ...)), ...)

    def __post_init__(self):
        # published lists and dicts arrive from JSON: keep the config
        # hashable (it is a static argument of jitted closures)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        rp = self.rope_parameters
        if isinstance(rp, dict):
            rp = tuple((kind, tuple(sorted(sec.items())))
                       for kind, sec in sorted(rp.items()))
        object.__setattr__(self, "rope_parameters", tuple(rp))
        if self.layer_types and len(self.layer_types) < self.n_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.n_layers} layers")

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0


# a full-attention layer's window: no position is that far back
NO_WINDOW = 1 << 30
# the expert stacks a serving closure reads in place (not scanned)
EXPERT_STACKS = ("we_gate", "we_up", "we_down")


def layer_data(cfg: TransformerConfig) -> dict:
    """Per-layer data the layer scans carry beside the stacked weights, so
    one scan body serves every layer kind: the layer's index (MoE: the
    programmed expert stacks are read in place at it) and, where the
    config has ``layer_types``, each layer's attention window and rope
    table (inverse frequencies and cos/sin scale). Empty for a model with
    neither, whose scans are then what they were without it."""
    out = {}
    if cfg.is_moe:
        out["layer"] = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    if cfg.layer_types:
        kinds = cfg.layer_types[:cfg.n_layers]
        sections = {kind: dict(sec) for kind, sec in cfg.rope_parameters}
        default = {"rope_type": "default", "rope_theta": cfg.rope_theta}
        tables = [rope_params(cfg.hd, sections.get(kind, default))
                  for kind in kinds]
        for kind in kinds:
            if kind not in ("sliding_attention", "full_attention"):
                raise ValueError(f"unknown layer type {kind!r}")
        if "sliding_attention" in kinds and cfg.sliding_window < 1:
            raise ValueError("sliding_attention layers need sliding_window")
        out["window"] = jnp.asarray(
            [cfg.sliding_window if kind == "sliding_attention" else NO_WINDOW
             for kind in kinds], jnp.int32)
        out["inv_freq"] = jnp.asarray(np.stack([t[0] for t in tables]))
        out["rope_scale"] = jnp.asarray([t[1] for t in tables], jnp.float32)
    return out


def _split_experts(blocks: dict):
    """(scanned blocks, expert stacks read in place): programmed expert
    stacks leave the scan's inputs, so the scan copies no layer slice of
    them; raw expert weights stay scanned."""
    inplace = {n: blocks[n] for n in EXPERT_STACKS
               if isinstance(blocks.get(n), AimcLinearState)}
    return {n: v for n, v in blocks.items() if n not in inplace}, inplace


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(key, cfg: TransformerConfig, dtype=jnp.float32) -> dict:
    l, d, hq, hkv, hd, ff = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                             cfg.n_kv_heads, cfg.hd, cfg.d_ff)
    ks = jax.random.split(key, 16)

    def stack(rng, k, n):
        return jax.vmap(lambda r: dense_init(r, k, n, dtype))(
            jax.random.split(rng, l))

    params: dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab, d, dtype),
        "final_norm": jnp.ones((d,), dtype),
        "blocks": {
            "ln1": jnp.ones((l, d), dtype),
            "ln2": jnp.ones((l, d), dtype),
            "wq": stack(ks[1], d, hq * hd),
            "wk": stack(ks[2], d, hkv * hd),
            "wv": stack(ks[3], d, hkv * hd),
            "wo": stack(ks[4], hq * hd, d),
        },
    }
    if cfg.qkv_bias:
        params["blocks"] |= {
            "bq": jnp.zeros((l, hq * hd), dtype),
            "bk": jnp.zeros((l, hkv * hd), dtype),
            "bv": jnp.zeros((l, hkv * hd), dtype),
        }
    if cfg.is_moe:
        e = cfg.n_experts

        def estack(rng, k, n):
            return jax.vmap(lambda r: jax.vmap(
                lambda r2: dense_init(r2, k, n, dtype))(jax.random.split(r, e))
            )(jax.random.split(rng, l))

        params["blocks"] |= {
            "router": stack(ks[5], d, e),
            "we_gate": estack(ks[6], d, ff),
            "we_up": estack(ks[7], d, ff),
            "we_down": estack(ks[8], ff, d),
        }
        if cfg.moe_dense_residual:
            dff = cfg.moe_dense_ff or ff
            params["blocks"] |= {
                "wd_gate": stack(ks[9], d, dff),
                "wd_up": stack(ks[10], d, dff),
                "wd_down": stack(ks[11], dff, d),
            }
    else:
        params["blocks"] |= {
            "w_gate": stack(ks[9], d, ff),
            "w_up": stack(ks[10], d, ff),
            "w_down": stack(ks[11], ff, d),
        }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(ks[12], d, cfg.vocab, dtype)
    return params


# ---------------------------------------------------------------------------
# block
# ---------------------------------------------------------------------------

def fuse_gate_stacks(params):
    """Post-`install()` rewrite: stack programmed same-shape projection
    groups into `[G, ...]` gate stacks so each group runs as ONE gate-fused
    multi-MVM kernel launch (kernel v2) per block:

      wq + wk + wv     -> wqkv  (MHA only — GQA K/V widths differ)
      w_gate + w_up    -> w_gu  (dense SwiGLU FFN)

    Gates stack at axis=1 (inside the layer-scan dim). Groups that are not
    all programmed `AimcLinearState`s of one shape pass through unchanged;
    outputs are bit-equal to the unfused path (noise off)."""
    blocks = dict(params["blocks"])
    for stacked_name, names in (("wqkv", ("wq", "wk", "wv")),
                                ("w_gu", ("w_gate", "w_up"))):
        leaves = [blocks.get(nm) for nm in names]
        if not all(isinstance(lf, AimcLinearState) for lf in leaves):
            continue
        if len({(lf.k, lf.n, lf.w_q.shape) for lf in leaves}) != 1:
            continue
        blocks[stacked_name] = stack_states([blocks.pop(nm) for nm in names],
                                            axis=1)
    return dict(params, blocks=blocks)


def _qkv(h, blk, cfg, exe, keys, positions, ld):
    """q, k, v of one block; ``ld`` is the layer's `layer_data` (its rope
    table, where the config has one)."""
    b, s, d = h.shape
    if "wqkv" in blk:      # gate-fused stack (fuse_gate_stacks, MHA)
        biases = (jnp.stack([blk["bq"], blk["bk"], blk["bv"]])
                  if "bq" in blk else None)
        q, k, v = linear_stack(h, blk["wqkv"], exe, keys[0], biases=biases)
    else:
        q = linear(h, blk["wq"], exe, keys[0], blk.get("bq"))
        k = linear(h, blk["wk"], exe, keys[1], blk.get("bk"))
        v = linear(h, blk["wv"], exe, keys[2], blk.get("bv"))
    table = (ld.get("inv_freq"), ld.get("rope_scale"))
    q = rope(q.reshape(b, s, cfg.n_heads, cfg.hd), positions, cfg.rope_theta,
             *table)
    k = rope(k.reshape(b, s, cfg.n_kv_heads, cfg.hd), positions,
             cfg.rope_theta, *table)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    # Megatron-style TP: Q heads sharded over `model` (skipped when the head
    # count does not divide); GQA K/V usually have too few heads to shard.
    # At decode (s == 1) q stays replicated over `model` instead — the KV
    # cache shards its sequence axis there (flash-decoding, layers.py).
    if s > 1:
        q = shard_act(q, model_dim=2)
        k = shard_act(k, model_dim=2)
        v = shard_act(v, model_dim=2)
    else:
        q, k, v = shard_act(q), shard_act(k), shard_act(v)
    return q, k, v


def _ffn(h2, blk, cfg: TransformerConfig, exe: Execution, keys, *,
         serve: bool = False, experts=None, layer=None):
    """The block's FFN -> (out, aux). MoE: training routes with capacity
    dispatch and ``aux`` is its load-balancing loss. ``serve`` routes
    dropless where `moe.serves_dropless` says so (`moe.moe_serve`, with
    ``experts`` read in place at ``layer`` where given) and ``aux`` counts
    the experts touched; where capacity dispatch serves, ``aux`` is None."""
    if not cfg.is_moe:
        if "w_gu" in blk:  # gate-fused stack (fuse_gate_stacks)
            g, u = linear_stack(h2, blk["w_gu"], exe, keys[4])
            # same activation-sharding constraints the unfused swiglu applies
            g = shard_act(g, model_dim=h2.ndim - 1)
            u = shard_act(u, model_dim=h2.ndim - 1)
            return linear(jax.nn.silu(g) * u, blk["w_down"], exe, keys[5]), 0.0
        return swiglu(h2, blk["w_gate"], blk["w_up"], blk["w_down"], exe,
                      keys[4]), 0.0
    b, s, d = h2.shape
    w = [(experts or {}).get(n, blk.get(n)) for n in EXPERT_STACKS]
    if serve and moe_lib.serves_dropless(w[0], exe):
        y, aux = moe_lib.moe_serve(h2.reshape(b * s, d), blk["router"], *w,
                                   top_k=cfg.top_k, exe=exe, layer=layer)
    else:
        y, aux = moe_lib.moe_ffn(
            h2.reshape(b * s, d), blk["router"], *w, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, exe=exe, key=keys[4])
        aux = None if serve else aux
    y = y.reshape(b, s, d)
    if cfg.moe_dense_residual:
        y = y + swiglu(h2, blk["wd_gate"], blk["wd_up"], blk["wd_down"],
                       exe, keys[5])
    return y, aux


def block_forward(h, blk, cfg: TransformerConfig, exe: Execution, key,
                  positions, ld=None):
    ld = ld or {}
    keys = list(jax.random.split(key, 6)) if key is not None else [None] * 6
    h = shard_act(h)
    q, k, v = _qkv(rmsnorm(h, blk["ln1"], cfg.norm_eps), blk, cfg, exe, keys,
                   positions, ld)
    att = flash_attention(q, k, v, causal=True, window=ld.get("window"),
                          q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    b, s, _ = h.shape
    h = h + linear(att.reshape(b, s, -1), blk["wo"], exe, keys[3])
    h = shard_act(h)
    ff, aux = _ffn(rmsnorm(h, blk["ln2"], cfg.norm_eps), blk, cfg, exe, keys)
    return h + ff, aux


# ---------------------------------------------------------------------------
# full forward (training / prefill-style)
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg: TransformerConfig, exe: Execution,
                 patch_embeds=None):
    h = jnp.take(params["embed"], tokens, axis=0).astype(exe.cdtype)
    if cfg.n_patches and patch_embeds is not None:
        # VLM frontend stub: positions [0, n_patches) carry precomputed
        # InternViT patch embeddings instead of token embeddings.
        h = jnp.concatenate(
            [patch_embeds.astype(exe.cdtype), h[:, cfg.n_patches:]], axis=1)
    return h


def forward(params, tokens, cfg: TransformerConfig, exe: Execution = None,
            rng=None, patch_embeds=None, return_hidden: bool = False):
    """tokens: [B, S] -> logits [B, S, V] (plus MoE aux loss).

    return_hidden=True returns the post-norm hidden states instead of logits
    (the train loop computes cross-entropy in vocab chunks — a [B,S,150k]
    logits tensor must never materialize)."""
    exe = exe or Execution()
    b, s = tokens.shape
    h = embed_tokens(params, tokens, cfg, exe, patch_embeds)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    layer_keys = (jax.random.split(rng, cfg.n_layers) if rng is not None
                  else jnp.zeros((cfg.n_layers, 2), jnp.uint32))

    @jax.checkpoint
    def body(carry, xs):
        h, aux = carry
        blk, lk, ld = xs
        key = lk if rng is not None else None
        h, a = block_forward(h, blk, cfg, exe, key, positions, ld)
        return (h, aux + a), None

    (h, aux), _ = jax.lax.scan(body, (h, 0.0),
                               (params["blocks"], layer_keys,
                                layer_data(cfg)))
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return h, aux
    unembed = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = h.astype(jnp.float32) @ as_weight(unembed, jnp.float32)
    return logits, aux


def unembed_matrix(params, cfg: TransformerConfig):
    return params["embed"].T if cfg.tie_embeddings else params["unembed"]


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode with KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16, shardings=None) -> dict:
    """KV cache [L, B, S, H, hd] + per-row lengths. ``shardings`` (a matching
    tree of `NamedSharding`s) creates each leaf directly on its mesh
    placement — the sharded serving engine's slot cache is born distributed
    instead of allocated replicated and moved (host-side callers only;
    inside jit leave it None)."""
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.hd)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
             "len": jnp.zeros((batch,), jnp.int32)}
    if shardings is not None:
        cache = jax.tree.map(jax.device_put, cache, shardings)
    return cache


def prefill(params, tokens, cfg: TransformerConfig, exe: Execution = None,
            max_seq: int | None = None, patch_embeds=None,
            cache_dtype=jnp.bfloat16, valid_len=None):
    """Full-sequence forward that also materializes the KV cache.

    ``valid_len`` ([B] int32) serves ragged prompts at one padded shape (the
    engine's shape-stability contract): tokens at positions >= valid_len are
    right-padding, the returned logits are gathered at each row's own last
    valid position, and the cache lengths are set per row — decode then
    masks attention with the ragged ``len`` and overwrites the padding K/V
    slots as real tokens arrive."""
    exe = exe or Execution()
    b, s = tokens.shape
    max_seq = max_seq or s
    h = embed_tokens(params, tokens, cfg, exe, patch_embeds)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    blocks, experts = _split_experts(params["blocks"])

    @jax.named_scope(tracing.BLOCK)
    def body(h, xs):
        blk, ld = xs
        keys = [None] * 6
        q, k, v = _qkv(rmsnorm(h, blk["ln1"], cfg.norm_eps), blk, cfg, exe,
                       keys, positions, ld)
        with jax.named_scope(tracing.ATTENTION):
            att = flash_attention(q, k, v, causal=True,
                                  window=ld.get("window"),
                                  q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        h = h + linear(att.reshape(b, s, -1), blk["wo"], exe, keys[3])
        ff, _ = _ffn(rmsnorm(h, blk["ln2"], cfg.norm_eps), blk, cfg, exe, keys,
                     serve=True, experts=experts, layer=ld.get("layer"))
        with jax.named_scope(tracing.KV_WRITE):
            kc = jnp.zeros((b, max_seq, cfg.n_kv_heads, cfg.hd), cache_dtype)
            vc = jnp.zeros((b, max_seq, cfg.n_kv_heads, cfg.hd), cache_dtype)
            kc = jax.lax.dynamic_update_slice(kc, k.astype(cache_dtype),
                                              (0, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, v.astype(cache_dtype),
                                              (0, 0, 0, 0))
        return h + ff, (kc, vc)

    with jax.named_scope(tracing.LAYERS):
        h, (ks, vs) = jax.lax.scan(body, h, (blocks, layer_data(cfg)))
    with jax.named_scope(tracing.HEAD):
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        unembed = (params["embed"].T if cfg.tie_embeddings
                   else params["unembed"])
        if valid_len is None:
            h_last = h[:, -1:]
            lens = jnp.full((b,), s, jnp.int32)
        else:
            lens = valid_len.astype(jnp.int32)
            idx = jnp.clip(lens - 1, 0, s - 1)
            h_last = h[jnp.arange(b), idx][:, None]              # [B, 1, D]
        logits = h_last.astype(jnp.float32) @ as_weight(unembed, jnp.float32)
    cache = {"k": ks, "v": vs, "len": lens}
    return logits, cache


def decode_step(params, cache, tokens, cfg: TransformerConfig,
                exe: Execution = None, ragged: bool = False, active=None,
                count_experts: bool = False):
    """tokens: [B, 1] one new token per sequence -> (logits [B,1,V], cache).

    ``ragged=False`` is the lockstep fast path (decode_32k/long_500k cells:
    every sequence is at the same position, so one dynamic_update_slice
    writes the whole batch). ``ragged=True`` is the continuous-batching
    contract: each row writes its K/V at its OWN ``cache["len"]`` position
    (a row scatter) and attends over its own valid length — slots
    prefilled at different times decode side by side in one batch.

    The layer scan writes the new K/V row into its local layer slice, so
    attention sees the current token, but emits only the rows; they reach
    the stacked cache after the scan, in place (`_scatter_kv`), and the
    step never rewrites the whole cache. ``active`` ([B] bool) freezes the
    lanes it marks False: their rows are dropped and their ``len`` holds —
    bit for bit what a select of the old cache over those lanes gave. With
    ``active=None`` every lane writes and advances.

    ``count_experts`` (MoE served dropless, `moe.serves_dropless`) also
    returns the experts each layer's step touched, summed over the layers
    (int32): (logits, cache, touched)."""
    exe = exe or Execution()
    b = tokens.shape[0]
    h = jnp.take(params["embed"], tokens, axis=0).astype(exe.cdtype)
    positions = cache["len"][:, None]                              # [B, 1]
    max_seq = cache["k"].shape[2]
    pos0 = cache["len"][0]
    row_idx = jnp.clip(cache["len"], 0, max_seq - 1)               # [B]
    lanes = jnp.arange(b)

    blocks, experts = _split_experts(params["blocks"])

    @jax.named_scope(tracing.BLOCK)
    def body(h, xs):
        blk, kc, vc, ld = xs
        keys = [None] * 6
        q, k, v = _qkv(rmsnorm(h, blk["ln1"], cfg.norm_eps), blk, cfg, exe,
                       keys, positions, ld)
        with jax.named_scope(tracing.KV_WRITE):
            if ragged:        # a row scatter, not a one-hot multiply
                kc = kc.at[lanes, row_idx].set(k[:, 0].astype(kc.dtype))
                vc = vc.at[lanes, row_idx].set(v[:, 0].astype(vc.dtype))
            else:
                kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                                  (0, pos0, 0, 0))
                vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                                  (0, pos0, 0, 0))
        with jax.named_scope(tracing.ATTENTION):
            att = decode_attention(q, kc, vc, kv_len=cache["len"] + 1,
                                   window=ld.get("window"))
        h = h + linear(att.reshape(b, 1, -1), blk["wo"], exe, keys[3])
        ff, touched = _ffn(rmsnorm(h, blk["ln2"], cfg.norm_eps), blk, cfg,
                           exe, keys, serve=True, experts=experts,
                           layer=ld.get("layer"))
        rows = (k[:, 0], v[:, 0])
        if not count_experts:
            return h + ff, rows
        if not cfg.is_moe or touched is None:
            raise ValueError("count_experts: only dropless routing counts "
                             "the experts it touches")
        return h + ff, rows + (touched,)

    with jax.named_scope(tracing.LAYERS):
        h, (ks, vs, *touched) = jax.lax.scan(
            body, h, (blocks, cache["k"], cache["v"], layer_data(cfg)))
    with jax.named_scope(tracing.KV_WRITE):
        # the lockstep write clamps at the cap as dynamic_update_slice does
        idx = row_idx if ragged else jnp.broadcast_to(
            jnp.clip(pos0, 0, max_seq - 1), (b,))
        if active is not None:
            idx = jnp.where(active, idx, max_seq)           # dropped rows
        new_cache = {"k": _scatter_kv(cache["k"], ks, idx),
                     "v": _scatter_kv(cache["v"], vs, idx),
                     "len": cache["len"] + (1 if active is None else
                                            active.astype(jnp.int32))}
    with jax.named_scope(tracing.HEAD):
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        unembed = (params["embed"].T if cfg.tie_embeddings
                   else params["unembed"])
        logits = h.astype(jnp.float32) @ as_weight(unembed, jnp.float32)
    if count_experts:
        return logits, new_cache, jnp.sum(touched[0])
    return logits, new_cache


def _scatter_kv(cache, rows, idx):
    """cache: [L, B, S, H, D]; rows: [L, B, H, D]; idx: [B] write positions
    (an index of ``S`` or more drops that lane's rows).

    One row scatter per layer, with the layer index static, updates the
    stacked cache in place: L x B rows written, nothing else read or
    copied. A single scatter over ``[:, lanes, idx]`` is NOT equivalent in
    cost — XLA's layout assignment gives it whole-cache copies."""
    lanes = jnp.arange(cache.shape[1])
    rows = rows.astype(cache.dtype)
    for layer in range(cache.shape[0]):
        cache = cache.at[layer, lanes, idx].set(rows[layer], mode="drop")
    return cache


# ---------------------------------------------------------------------------
# serving: paged KV cache (DESIGN.md §15)
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: TransformerConfig, n_pages: int, page_size: int,
                     dtype=jnp.float32, shardings=None) -> dict:
    """Physical K/V page pools [L, n_pages, P, Hkv, hd].

    Page 0 is the engine's scratch page (`runtime.pages.SCRATCH`): traced
    writes for inactive lanes land there and are never read unmasked. A
    slot's logical cache is the gather of its page-table row (`paged_view`);
    memory scales with pages actually allocated, not slots x max_seq."""
    shape = (cfg.n_layers, n_pages, page_size, cfg.n_kv_heads, cfg.hd)
    pools = {"kp": jnp.zeros(shape, dtype), "vp": jnp.zeros(shape, dtype)}
    if shardings is not None:
        pools = jax.tree.map(jax.device_put, pools, shardings)
    return pools


def paged_view(kp, vp, pt, max_seq: int):
    """Gather the dense per-slot K/V view out of the page pools.

    kp/vp: [L, n_pages, P, H, hd]; pt: [S, M] page table -> k/v
    [L, S, max_seq, H, hd]. Rows past a slot's length map through whatever
    page the (possibly stale) table names — `decode_attention` masks
    everything at or beyond ``kv_len`` to exact 0.0 before the softmax, so
    garbage rows never contribute a bit (the §15 equality argument)."""
    l, _, p, h, hd = kp.shape
    s, m = pt.shape
    k = kp[:, pt].reshape(l, s, m * p, h, hd)[:, :, :max_seq]
    v = vp[:, pt].reshape(l, s, m * p, h, hd)[:, :, :max_seq]
    return k, v


def prefill_chunk(params, tokens, cfg: TransformerConfig, exe: Execution,
                  kp, vp, pt_row, pos0, span, *, page_size: int,
                  context_len: int):
    """One bounded prefill leg writing straight into the page pools.

    tokens: [1, C] — the leg's token window (rows past ``span`` are junk
    padding on the final leg); ``pt_row``: [M] this request's page table
    row; ``pos0``/``span``: traced absolute start + valid width. Earlier
    legs' K/V are read back from the pools (cache dtype must be float32 so
    the readback is bit-identical to the producing leg's activations — the
    engine enforces this), the leg attends with ``q_offset=pos0`` over
    exactly ``context_len`` rows (= the dense engine's prompt_pad, so the
    flash-attention chunk reduction order matches dense prefill bitwise),
    and touched pages [pos0//P, (pos0+span-1)//P] are scattered back;
    untouched page indices route to the scratch page 0. Returns
    ``(tok [1,1], kp, vp)`` — tok is argmax at the leg's last valid row,
    meaningful on the final leg only."""
    b, c = tokens.shape
    m = pt_row.shape[0]
    hkv, hd = cfg.n_kv_heads, cfg.hd
    n_rows = m * page_size
    h = embed_tokens(params, tokens, cfg, exe)
    positions = pos0 + jnp.broadcast_to(jnp.arange(c), (b, c))
    j = jnp.arange(m)
    j0 = pos0 // page_size
    j1 = (pos0 + span - 1) // page_size
    pids = jnp.where((j >= j0) & (j <= j1), pt_row, 0)

    def body(h, xs):
        blk, kpl, vpl, ld = xs
        keys = [None] * 6
        q, k, v = _qkv(rmsnorm(h, blk["ln1"], cfg.norm_eps), blk, cfg, exe,
                       keys, positions, ld)
        kc = kpl[pt_row].reshape(n_rows, hkv, hd)
        vc = vpl[pt_row].reshape(n_rows, hkv, hd)
        # extend by C rows so the slice write never clamps at the pool edge
        kx = jnp.concatenate([kc, jnp.zeros((c, hkv, hd), kc.dtype)])
        vx = jnp.concatenate([vc, jnp.zeros((c, hkv, hd), vc.dtype)])
        kx = jax.lax.dynamic_update_slice_in_dim(
            kx, k[0].astype(kx.dtype), pos0, axis=0)
        vx = jax.lax.dynamic_update_slice_in_dim(
            vx, v[0].astype(vx.dtype), pos0, axis=0)
        att = flash_attention(q, kx[None, :context_len], vx[None, :context_len],
                              causal=True, q_offset=pos0,
                              window=ld.get("window"),
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        h = h + linear(att.reshape(b, c, -1), blk["wo"], exe, keys[3])
        ff, _ = _ffn(rmsnorm(h, blk["ln2"], cfg.norm_eps), blk, cfg, exe, keys,
                     serve=True)
        kpl = kpl.at[pids].set(kx[:n_rows].reshape(m, page_size, hkv, hd))
        vpl = vpl.at[pids].set(vx[:n_rows].reshape(m, page_size, hkv, hd))
        return h + ff, (kpl, vpl)

    h, (kp, vp) = jax.lax.scan(body, h, (params["blocks"], kp, vp,
                                         layer_data(cfg)))
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    unembed = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    idx = jnp.broadcast_to(jnp.clip(span - 1, 0, c - 1), (b,))
    h_last = h[jnp.arange(b), idx][:, None]
    logits = h_last.astype(jnp.float32) @ as_weight(unembed, jnp.float32)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    return tok, kp, vp
