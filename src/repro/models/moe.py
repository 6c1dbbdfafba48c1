"""Mixture-of-Experts FFN (arctic-480b: 128e top-2 + dense residual;
olmoe-1b-7b: 64e top-8; mellum2-12b: 64e top-8).

Serving (`moe_serve`) routes dropless: every token goes through all of its
top-k experts, whatever its batch mates chose, so a request's output does
not depend on what it is batched with. The routed rows are sorted by
expert; programmed expert stacks then run as ONE grouped crossbar call per
projection group (`core.aimc.aimc_apply_experts`, reading the stacked codes
in place at the layer index), and raw expert weights as a ragged matmul.

Training (`moe_ffn`) keeps capacity dispatch: two capacity-bucketed
dispatch strategies, both deterministic-shape and
dry-run friendly, experts sharded over the `model` mesh axis:

  * ``sort``   (default; §Perf iteration 2) — argsort tokens by expert,
    compute capacity ranks from segment starts, scatter into the [E, C, d]
    expert buffers and gather back for the combine. Dispatch costs ~zero
    FLOPs and never materializes a [T, E, C] tensor.
  * ``einsum`` (t5x/flaxformer style; the measured baseline) — one-hot
    dispatch/combine einsums. 2*T*E*C*d FLOPs per einsum: measured 45x the
    useful compute on olmoe (EXPERIMENTS.md §Perf). Kept selectable via
    REPRO_MOE_EINSUM=1 for baseline reproduction.

The router (softmax/top-k) stays digital — it is an activation-on-activation
op, outside the AIMC applicability boundary — while each expert's FFN weights
are stationary matrices and therefore AIMC-mapped (vmapped crossbar
programming per expert; see DESIGN.md §4: experts are ideal crossbar tenants,
mirroring the paper's many-small-matrices-per-tile packing).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core.aimc import AimcLinearState, aimc_apply_experts
from repro.models.layers import (Execution, _current_mesh, as_weight, linear,
                                 shard_act)


def moe_ffn(x, router_w, w_gate, w_up, w_down, *, top_k: int,
            capacity_factor: float, exe: Execution, key=None):
    """x: [T, d]. Expert weights: [E, d, ff] / [E, ff, d]. Returns ([T, d], aux).

    aux = load-balancing loss (Switch-style: E * sum_e f_e * p_e).
    """
    t, d = x.shape
    e = router_w.shape[1]
    cap = max(1, int(t * top_k / e * capacity_factor))

    # ---- router (digital) --------------------------------------------------
    gate_vals, gate_idx, probs = route(x, router_w, top_k)

    # load-balance aux (computed before capacity truncation)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(gate_idx, e), axis=1), axis=0)
    aux = e * jnp.sum(me * ce)

    xd = x.astype(exe.cdtype)
    if not os.environ.get("REPRO_MOE_EINSUM"):
        y = _moe_sharded(xd, gate_idx, gate_vals, w_gate, w_up, w_down,
                         e, cap, top_k, exe, key)
        if y is not None:
            return y.astype(exe.cdtype), aux
    if os.environ.get("REPRO_MOE_EINSUM"):
        xe, combine = _dispatch_einsum(xd, gate_idx, gate_vals, e, cap, top_k,
                                       exe)
        slot_o = None
    else:
        xe, slot_o = _dispatch_sort(xd, gate_idx, e, cap, top_k)
        combine = None
    xe = shard_act(xe, model_dim=0)        # experts over `model` (EP)

    # ---- expert FFNs (AIMC-mapped when exe.mode == "aimc") -----------------
    # Each expert is its own crossbar tenant. Programmed (AimcLinearState)
    # expert stacks run apply-only under vmap; raw weights in aimc mode run
    # the per-call STE (noise-aware training). `layers.linear` dispatches.
    if isinstance(w_gate, AimcLinearState) or exe.mode == "aimc":
        use_keys = key is not None
        keys = (jax.random.split(key, e * 3).reshape(e, 3, 2) if use_keys
                else jnp.zeros((e, 3, 2), jnp.uint32))

        def one_expert(xi, wg, wu, wd, ks):
            k0, k1, k2 = ((ks[0], ks[1], ks[2]) if use_keys
                          else (None, None, None))
            g = linear(xi, wg, exe, k0)
            u = linear(xi, wu, exe, k1)
            return linear(jax.nn.silu(g) * u, wd, exe, k2)

        ye = jax.vmap(one_expert)(xe, w_gate, w_up, w_down, keys)
    else:
        g = jnp.einsum("ecd,edf->ecf", xe, as_weight(w_gate, exe.cdtype))
        u = jnp.einsum("ecd,edf->ecf", xe, as_weight(w_up, exe.cdtype))
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                        as_weight(w_down, exe.cdtype))

    if combine is not None:                # einsum combine (baseline)
        y = jnp.einsum("tec,ecd->td", combine.astype(exe.cdtype),
                       ye.astype(exe.cdtype))
    else:                                  # gather combine (sort dispatch)
        ye_flat = jnp.concatenate(
            [ye.astype(exe.cdtype).reshape(e * cap, d),
             jnp.zeros((1, d), exe.cdtype)], axis=0)
        y = jnp.einsum("tk,tkd->td", gate_vals.astype(exe.cdtype),
                       ye_flat[slot_o])
    return y.astype(exe.cdtype), aux


def route(x, router_w, top_k: int):
    """Float32 softmax router: (gates [T, k] renormalised over the top k,
    expert ids [T, k], probabilities [T, E])."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    return gate_vals, gate_idx, probs


def serves_dropless(w_gate, exe: Execution, mesh=None) -> bool:
    """Whether serving routes these experts dropless (`moe_serve`).

    Programmed stacks always do. Raw weights do in digital mode, except
    where a `model` axis of ``mesh`` (default: the active one) shards them:
    `moe_serve` has no expert-parallel layout, so there they keep
    `moe_ffn`'s expert-parallel capacity dispatch (`_moe_sharded`). Raw
    weights in AIMC mode run `moe_ffn`'s per-expert crossbar emulation."""
    if isinstance(w_gate, AimcLinearState):
        return True
    if exe.mode == "aimc":
        return False
    mesh = mesh if mesh is not None else _current_mesh()
    return mesh is None or dict(mesh.shape).get("model", 1) == 1


def expert_block_rows(rows: int, n_experts: int) -> int:
    """Rows per block of the grouped layout: the mean group size rounded up
    to the 8-row sublane tile, at most 128 (8 in a decode step of 32 lanes
    x top-8 over 64 experts, 128 in a prefill of 1536 rows)."""
    mean = -(-rows // n_experts)
    return min(128, -(-mean // 8) * 8)


def moe_serve(x, router_w, w_gate, w_up, w_down, *, top_k: int,
              exe: Execution, layer=None):
    """Dropless expert FFN for serving. x: [T, d] -> ([T, d], touched).

    Programmed experts (`AimcLinearState` stacks ``[L, E, ...]``) are read
    in place at ``layer``; raw expert weights arrive as this layer's
    ``[E, d, ff]`` / ``[E, ff, d]``. ``touched`` (int32) counts the experts
    that received at least one row."""
    t, d = x.shape
    with jax.named_scope(tracing.MOE_ROUTER):
        gate_vals, gate_idx, _ = route(x, router_w, top_k)
    programmed = isinstance(w_gate, AimcLinearState)
    e = w_gate.w_q.shape[-4] if programmed else w_gate.shape[0]
    n = t * top_k
    with jax.named_scope(tracing.MOE_DISPATCH):
        ids = gate_idx.reshape(-1)                               # [T*k]
        order = jnp.argsort(ids, stable=True)                    # by expert
        sizes = jnp.zeros((e,), jnp.int32).at[ids].add(1)
        touched = jnp.sum(sizes > 0).astype(jnp.int32)
        xs = x[order // top_k]                                   # sorted rows
    if programmed:
        y_pad, dest = _grouped_crossbar(xs, ids[order], sizes, w_gate, w_up,
                                        w_down, layer, exe)
    else:
        with jax.named_scope(tracing.EXPERTS):
            cd = exe.cdtype
            g = jax.lax.ragged_dot(xs.astype(cd), as_weight(w_gate, cd),
                                   sizes)
            u = jax.lax.ragged_dot(xs.astype(cd), as_weight(w_up, cd), sizes)
            y_sorted = jax.lax.ragged_dot(jax.nn.silu(g) * u,
                                          as_weight(w_down, cd), sizes)
    with jax.named_scope(tracing.MOE_COMBINE):
        if programmed:
            y_sorted = y_pad[dest]
        y_rows = jnp.zeros((n, d), y_sorted.dtype).at[order].set(y_sorted)
        y = jnp.einsum("tk,tkd->td", gate_vals.astype(jnp.float32),
                       y_rows.reshape(t, top_k, d).astype(jnp.float32))
    return y.astype(exe.cdtype), touched


def _grouped_crossbar(xs, sorted_ids, sizes, w_gate, w_up, w_down, layer,
                      exe: Execution):
    """The sorted rows [n, d] through their experts' programmed gate/up and
    down stacks: each expert's rows padded to whole blocks, one grouped
    kernel call for gate+up (shared input and DAC scale), one for down.
    Returns the padded layout's outputs and each sorted row's place in it."""
    n, d = xs.shape
    e = sizes.shape[0]
    bm = expert_block_rows(n, e)
    with jax.named_scope(tracing.MOE_DISPATCH):
        padded = -(-sizes // bm) * bm
        ends = jnp.cumsum(padded)
        starts = ends - padded
        rank = jnp.arange(n) - (jnp.cumsum(sizes) - sizes)[sorted_ids]
        dest = starts[sorted_ids] + rank                         # [n]
        nb = -(-(n + e * (bm - 1)) // bm)
        x_pad = jnp.zeros((nb * bm, d), xs.dtype).at[dest].set(xs)
        n_used = ends[-1] // bm
        blocks = jnp.searchsorted(ends, jnp.arange(nb) * bm, side="right")
        last = blocks[jnp.maximum(n_used - 1, 0)]
        blocks = jnp.where(jnp.arange(nb) < n_used, blocks, last)
        blocks = blocks.astype(jnp.int32)
    with jax.named_scope(tracing.EXPERTS):
        g, u = aimc_apply_experts((w_gate, w_up), x_pad, layer, blocks,
                                  n_used, exe.aimc, block_rows=bm)
        h = jax.nn.silu(g) * u
        (y,) = aimc_apply_experts((w_down,), h, layer, blocks, n_used,
                                  exe.aimc, block_rows=bm)
    return y, dest


def _moe_sharded(xd, gate_idx, gate_vals, w_gate, w_up, w_down,
                 e, cap, top_k, exe, key):
    """Expert parallelism with explicit locality (§Perf iteration 3).

    GSPMD lowers a cross-shard scatter/gather dispatch conservatively
    (measured: per-layer all-reduces of the full [E, C, d] buffer). Instead:

      1. shard_map DISPATCH — every (data, model) device sorts ITS token
         shard into a local [E, C/ndp, d] buffer. The buffer is computed
         redundantly across the `model` axis, so the subsequent
         replicated -> E-over-model re-shard is a free local slice: the
         "all-to-all" costs zero wire.
      2. expert FFN in SPMD land — xe 2-D sharded (E -> model, C -> data);
         the einsum is fully local; FSDP all-gathers only the expert weights.
      3. shard_map COMBINE — one all-gather of ye over `model` per layer
         (each data shard already owns its tokens' capacity rows), then a
         local gather at the capacity slots.

    Returns None when the shapes don't divide the active mesh (falls back to
    the single-device paths below).
    """
    from repro.models.layers import _current_mesh
    mesh = _current_mesh()
    if (mesh is None or "model" not in mesh.axis_names
            or exe.mode == "aimc" or isinstance(w_gate, AimcLinearState)):
        return None
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in mesh.axis_names if a != "model")
    ndp = 1
    for a in dp:
        ndp *= mesh.shape[a]
    nmodel = mesh.shape["model"]
    t, d = xd.shape
    cap_loc = max(1, cap // ndp)
    if ndp == 1 or t % ndp or e % nmodel:
        return None

    def disp_local(x_loc, ids_loc):
        xe_loc, slot_loc = _dispatch_sort(x_loc, ids_loc, e, cap_loc, top_k)
        return xe_loc, slot_loc

    xe, slot_o = jax.shard_map(
        disp_local, mesh=mesh,
        in_specs=(P(dp, None), P(dp, None)),
        out_specs=(P(None, dp, None), P(dp, None)),
        check_vma=False)(xd, gate_idx)
    # replicated-over-model -> E-sharded: a local slice, no communication
    xe = jax.lax.with_sharding_constraint(xe, P("model", dp, None))

    g = jnp.einsum("ecd,edf->ecf", xe, as_weight(w_gate, exe.cdtype))
    u = jnp.einsum("ecd,edf->ecf", xe, as_weight(w_up, exe.cdtype))
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                    as_weight(w_down, exe.cdtype))
    ye = jax.lax.with_sharding_constraint(ye, P("model", dp, None))

    def combine_local(ye_loc, slot_loc, gv_loc):
        ye_full = jax.lax.all_gather(ye_loc, "model", axis=0, tiled=True)
        ye_flat = jnp.concatenate(
            [ye_full.reshape(e * cap_loc, d),
             jnp.zeros((1, d), ye_full.dtype)], axis=0)
        return jnp.einsum("tk,tkd->td", gv_loc.astype(ye_full.dtype),
                          ye_flat[slot_loc])

    # check_vma=False: the model-axis all_gather makes the output
    # replicated over `model`, which the varying-axis checker cannot infer
    y = jax.shard_map(
        combine_local, mesh=mesh,
        in_specs=(P("model", dp, None), P(dp, None), P(dp, None)),
        out_specs=P(dp, None), check_vma=False)(ye, slot_o, gate_vals)
    return y


def _dispatch_sort(xd, gate_idx, e, cap, top_k):
    """Sort-based capacity dispatch: ~zero FLOPs, no [T, E, C] tensor.

    Returns (xe [E, C, d], slot_o [T, k]) where slot_o indexes the flattened
    [E*C (+1 overflow)] expert buffer for the combine gather; dropped
    (over-capacity) assignments point at the zero overflow row.
    """
    t, d = xd.shape
    ids = gate_idx.reshape(-1)                                # [T*k]
    order = jnp.argsort(ids, stable=True)                     # token-major
    sorted_ids = ids[order]
    starts = jnp.searchsorted(sorted_ids, jnp.arange(e))      # segment starts
    rank = jnp.arange(t * top_k) - jnp.take(starts, sorted_ids)
    slot_s = jnp.where(rank < cap, sorted_ids * cap + rank, e * cap)
    tok_s = order // top_k
    xe_flat = jnp.zeros((e * cap + 1, d), xd.dtype).at[slot_s].set(
        xd[tok_s], mode="drop")
    xe = xe_flat[: e * cap].reshape(e, cap, d)
    inv = jnp.argsort(order)                                  # original order
    slot_o = slot_s[inv].reshape(t, top_k)
    return xe, slot_o


def _dispatch_einsum(xd, gate_idx, gate_vals, e, cap, top_k, exe):
    """One-hot dispatch/combine (t5x style) — the measured baseline."""
    t, d = xd.shape
    flat_mask = jax.nn.one_hot(gate_idx.reshape(-1), e,
                               dtype=jnp.float32)                  # [T*k, E]
    pos = jnp.cumsum(flat_mask, axis=0) - flat_mask                # arrival rank
    pos = jnp.sum(pos * flat_mask, axis=-1)                        # [T*k]
    keep = flat_mask * (pos < cap)[:, None]                        # [T*k, E]
    cap_slot = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                              dtype=jnp.float32)                   # [T*k, C]
    keep = keep.reshape(t, top_k, e)
    cap_slot = cap_slot.reshape(t, top_k, cap)
    dispatch = jnp.einsum("tke,tkc->tec", keep, cap_slot)          # [T, E, C]
    combine = jnp.einsum("tke,tkc,tk->tec", keep, cap_slot, gate_vals)
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(exe.cdtype), xd)
    return xe, combine
