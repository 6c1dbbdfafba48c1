"""Step-function builders: train_step / prefill_step / serve_step per
(architecture x shape cell), plus ``input_specs`` — the ShapeDtypeStruct
stand-ins the multi-pod dry-run lowers against (no device allocation).

Memory discipline baked in here (DESIGN.md §5):
  * gradient accumulation: the global batch splits into microbatches scanned
    inside the jit (activation memory ~ one microbatch);
  * chunked cross-entropy: logits are materialized 512 sequence positions at
    a time (a [B, 4096, 152k] logits tensor would be ~20 GB/chip);
  * remat: every model scans remat-wrapped blocks;
  * serving params cast to bf16 (or int8 codes — `Execution.serve_int8`,
    the paper's number format, a §Perf variant).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ArchSpec, ShapeCell
from repro.launch.mesh import axis_size, dp_axes
from repro.launch.shardings import (batch_specs, cache_specs, fit_spec,
                                    fit_specs, get_opt_specs,
                                    get_param_specs, shard_aimc_states,
                                    strip_fsdp)
from repro.models.layers import Execution, at_stated_precision
from repro.optim import make_optimizer

CE_CHUNK = 512


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def chunked_ce(h, unembed, labels, chunk: int = CE_CHUNK):
    """Cross entropy over [B, S] without materializing [B, S, V].

    labels < 0 are masked (VLM patch positions). Returns (sum_loss, n_tok).
    """
    b, s, d = h.shape
    chunk = min(chunk, s)
    nc = s // chunk
    s_used = nc * chunk
    hc = h[:, :s_used].reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels[:, :s_used].reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        from repro.models.layers import shard_act
        hx, lx = xs
        logits = hx.astype(jnp.float32) @ unembed.astype(jnp.float32)
        # vocab-sharded logits: each model shard computes its vocab slice;
        # only the [B, chunk] logsumexp partials cross the mesh
        logits = shard_act(logits, model_dim=2)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lx, 0)[..., None], axis=-1)[..., 0]
        mask = (lx >= 0).astype(jnp.float32)
        loss = jnp.sum((logz - gold) * mask)
        return (carry[0] + loss, carry[1] + jnp.sum(mask)), None

    (loss, n), _ = jax.lax.scan(body, (0.0, 0.0), (hc, lc))
    return loss, n


# ---------------------------------------------------------------------------
# batch construction helpers (abstract + concrete share one shape source)
# ---------------------------------------------------------------------------

def batch_shapes(spec: ArchSpec, cell: ShapeCell) -> dict:
    """Logical [global] shapes+dtypes of one training/prefill batch."""
    b, s = cell.global_batch, cell.seq_len
    cfg = spec.model_cfg
    if spec.family == "audio":
        tgt = max(s // spec.tgt_ratio, 64)
        return {"frames": ((b, s, cfg.d_model), jnp.bfloat16),
                "tokens": ((b, tgt), jnp.int32),
                "labels": ((b, tgt), jnp.int32)}
    out = {"tokens": ((b, s), jnp.int32), "labels": ((b, s), jnp.int32)}
    if spec.family == "vlm":
        out["patch_embeds"] = ((b, spec.n_patches, cfg.d_model), jnp.bfloat16)
    return out


def batch_kind(spec: ArchSpec) -> str:
    return {"audio": "encdec", "vlm": "vlm"}.get(spec.family, "lm")


def abstract_batch(spec: ArchSpec, cell: ShapeCell) -> dict:
    return {k: jax.ShapeDtypeStruct(shp, dt)
            for k, (shp, dt) in batch_shapes(spec, cell).items()}


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StepBundle:
    """Everything the dry-run / launcher needs for one (arch, cell, mesh)."""
    fn: Callable                   # the step function to jit
    in_shardings: Any
    out_shardings: Any
    abstract_inputs: tuple         # ShapeDtypeStructs, positional
    donate_argnums: tuple = ()
    schedule: Any = None           # core.schedule.CoreSchedule, when serving
                                   # through a multi-core lowering


def _unwrap_program(program):
    """Serving steps accept either an `AimcProgram` or a multi-core
    `core.schedule.CoreSchedule`; installation always goes through the
    underlying program, and the schedule (when given) additionally
    column-shards the crossbar states and rides on the bundle for per-core
    ledger reporting (dry-run / serve stats)."""
    from repro.core.schedule import CoreSchedule
    if isinstance(program, CoreSchedule):
        return program.program, program
    return program, None


def _model_forward_hidden(model, spec, cfg, exe):
    """Uniform (params, batch, rng) -> (hidden, aux) across families."""
    fam = spec.family

    def fwd(params, batch, rng):
        if fam == "audio":
            return model.forward(params, batch, cfg, exe, rng,
                                 return_hidden=True)
        if fam == "vlm":
            return model.forward(params, batch["tokens"], cfg, exe, rng,
                                 patch_embeds=batch["patch_embeds"],
                                 return_hidden=True)
        return model.forward(params, batch["tokens"], cfg, exe, rng,
                             return_hidden=True)

    return fwd


def make_train_step(spec: ArchSpec, cell: ShapeCell, mesh,
                    exe: Execution = Execution(), lr_scale: float = 1.0):
    cfg = spec.model_cfg
    model = spec.model_module()
    opt_init, opt_update, _ = make_optimizer(spec.optimizer)
    dp = dp_axes(mesh)
    dp_total = axis_size(mesh, dp)
    micro_global = dp_total * spec.microbatch
    n_micro = max(1, cell.global_batch // micro_global)
    fwd = _model_forward_hidden(model, spec, cfg, exe)
    pdtype = jnp.dtype(spec.param_dtype)

    params_shape = jax.eval_shape(
        lambda k: jax.tree.map(lambda x: x.astype(pdtype),
                               model.init(k, cfg)), jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(opt_init, params_shape)
    pspecs = fit_specs(get_param_specs(params_shape, mesh), params_shape, mesh)
    ospecs = fit_specs(get_opt_specs(opt_shape, params_shape, mesh),
                       opt_shape, mesh)
    bspecs = fit_specs(batch_specs(mesh, batch_kind(spec)),
                       abstract_batch(spec, cell), mesh)

    def split_micro(x):
        mb = x.shape[0] // n_micro
        return jax.lax.with_sharding_constraint(
            x.reshape(n_micro, mb, *x.shape[1:]),
            P(None, dp, *([None] * (x.ndim - 1))))

    def train_step(params, opt_state, batch, rng):
        micro = jax.tree.map(split_micro, batch)

        def micro_loss(p, mb, key):
            h, aux = fwd(p, mb, key)
            unemb = model.unembed_matrix(p, cfg)
            loss_sum, n_tok = chunked_ce(h, unemb, mb["labels"])
            loss = loss_sum / jnp.maximum(n_tok, 1.0)
            return loss + 0.01 * aux, loss

        grad_fn = jax.value_and_grad(micro_loss, has_aux=True)

        def constrain_like_params(tree):
            # keep the accumulated grads sharded exactly like the FSDP params;
            # without this XLA replicates the scan carry (27 GB/device for
            # olmoe) and all-reduces instead of reduce-scattering.
            return jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(x, s),
                tree, pspecs, is_leaf=lambda x: x is None)

        def acc_body(carry, xs):
            g_acc, loss_acc, i = carry
            mb = xs
            key = jax.random.fold_in(rng, i)
            (_, loss), g = grad_fn(params, mb, key)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32) / n_micro, g_acc, g)
            g_acc = constrain_like_params(g_acc)
            return (g_acc, loss_acc + loss / n_micro, i + 1), None

        g0 = constrain_like_params(
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))
        (grads, loss, _), _ = jax.lax.scan(
            acc_body, (g0, 0.0, 0), micro, length=n_micro)
        new_params, new_opt, metrics = opt_update(grads, opt_state, params,
                                                  lr_scale)
        metrics = dict(metrics, loss=loss)
        return new_params, new_opt, metrics

    abstract = (params_shape, opt_shape, abstract_batch(spec, cell),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
    in_sh = (pspecs, ospecs, bspecs, P())
    out_sh = (pspecs, ospecs, None)
    return StepBundle(at_stated_precision(train_step, exe), in_sh, out_sh,
                      abstract, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------

def _serve_params_shape(model, spec, cfg, int8: bool = False):
    """Serving parameter shapes: bf16, or int8 codes + per-channel scales
    (the paper's number format; Execution.serve_int8)."""
    from repro.launch.shardings import (EXPERT_IN, EXPERT_OUT, IN_PROJ,
                                        OUT_PROJ)
    quantizable = IN_PROJ | OUT_PROJ | EXPERT_IN | EXPERT_OUT | {"unembed"}
    shape = jax.eval_shape(lambda k: model.init(k, cfg),
                           jax.random.PRNGKey(0))

    def conv(path, leaf):
        name = ""
        for k in reversed(path):
            if hasattr(k, "key"):
                name = str(k.key)
                break
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        if int8 and name in quantizable and leaf.ndim >= 2:
            return {"q": jax.ShapeDtypeStruct(leaf.shape, jnp.int8),
                    "s": jax.ShapeDtypeStruct(
                        leaf.shape[:-2] + (1, leaf.shape[-1]), jnp.float32)}
        return jax.ShapeDtypeStruct(leaf.shape, jnp.bfloat16)

    return jax.tree_util.tree_map_with_path(conv, shape)


def make_prefill_step(spec: ArchSpec, cell: ShapeCell, mesh,
                      exe: Execution = Execution(), program=None):
    cfg = spec.model_cfg
    model = spec.model_module()
    cache_dt = jnp.dtype(spec.cache_dtype)
    program, schedule = _unwrap_program(program)
    params_shape = _serve_params_shape(model, spec, cfg, int8=exe.serve_int8)
    if program is not None:     # program-once serving: mapped projections
        params_shape = program.install_shape(params_shape)  # are AIMC states
    pspecs = fit_specs(get_param_specs(params_shape, mesh), params_shape, mesh)
    if schedule is not None and schedule.n_cores > 1:
        # multi-core lowering: each device owns its cores' bit lines
        pspecs = shard_aimc_states(pspecs, params_shape, mesh)
    if exe.serve_int8:      # int8 weights replicate over data: no gathers
        pspecs = strip_fsdp(pspecs, mesh)
    bspecs = fit_specs(batch_specs(mesh, batch_kind(spec)),
                       abstract_batch(spec, cell), mesh)
    b, s = cell.global_batch, cell.seq_len

    # the model-facing prefill math lives in runtime.engine — one
    # implementation under both the static shape cells and the
    # continuous-batching ServeEngine
    from repro.runtime.engine import static_prefill_closure
    prefill = static_prefill_closure(model, cfg, exe, family=spec.family,
                                     module=spec.module, max_seq=s,
                                     cache_dtype=cache_dt)

    abstract_b = abstract_batch(spec, cell)
    cache_shape = jax.eval_shape(prefill, params_shape, abstract_b)[1]
    cspecs = (fit_specs(cache_specs(cache_shape, mesh), cache_shape, mesh)
              if cache_shape != () else ())
    dp = dp_axes(mesh)
    out_tok = fit_spec(P(dp, None), (b, 1), mesh)
    return StepBundle(prefill, (pspecs, bspecs), (out_tok, cspecs),
                      (params_shape, abstract_b), schedule=schedule)


def make_serve_step(spec: ArchSpec, cell: ShapeCell, mesh,
                    exe: Execution = Execution(), program=None):
    """One decode step against a seq_len KV cache (the decode_* cells)."""
    cfg = spec.model_cfg
    model = spec.model_module()
    cache_dt = jnp.dtype(spec.cache_dtype)
    program, schedule = _unwrap_program(program)
    params_shape = _serve_params_shape(model, spec, cfg, int8=exe.serve_int8)
    if program is not None:     # program-once serving (core.program)
        params_shape = program.install_shape(params_shape)
    pspecs = fit_specs(get_param_specs(params_shape, mesh), params_shape, mesh)
    if schedule is not None and schedule.n_cores > 1:
        # multi-core lowering: each device owns its cores' bit lines
        pspecs = shard_aimc_states(pspecs, params_shape, mesh)
    if exe.serve_int8:      # int8 weights replicate over data: no gathers
        pspecs = strip_fsdp(pspecs, mesh)
    b, s = cell.global_batch, cell.seq_len

    if spec.family == "audio":
        src = max(s // spec.tgt_ratio, 64)
        cache_shape = jax.eval_shape(
            lambda: model.init_cache(cfg, b, s, src, cache_dt))
    elif spec.module in ("rglru", "xlstm"):
        cache_shape = jax.eval_shape(
            lambda: model.init_cache(cfg, b, s, cache_dt))
    else:
        cache_shape = jax.eval_shape(
            lambda: model.init_cache(cfg, b, s, cache_dt))
    cspecs = fit_specs(cache_specs(cache_shape, mesh), cache_shape, mesh)
    dp = dp_axes(mesh)

    # the lockstep decode math shared with the engine (runtime.engine)
    from repro.runtime.engine import static_decode_closure
    serve_step = static_decode_closure(model, cfg, exe)

    tok_spec = fit_spec(P(dp, None), (b, 1), mesh)
    abstract = (params_shape, cache_shape,
                jax.ShapeDtypeStruct((b, 1), jnp.int32))
    in_sh = (pspecs, cspecs, tok_spec)
    out_sh = (tok_spec, cspecs)
    return StepBundle(serve_step, in_sh, out_sh, abstract,
                      donate_argnums=(1,), schedule=schedule)


# ---------------------------------------------------------------------------
# the front door
# ---------------------------------------------------------------------------

def make_step(spec: ArchSpec, cell: ShapeCell, mesh,
              exe: Execution = Execution(), program=None) -> StepBundle:
    """`program` (an `core.program.AimcProgram`, or a multi-core
    `core.schedule.CoreSchedule` wrapping one) selects program-once AIMC
    serving: the step's parameter tree carries the installed crossbar states
    (training cells reject it — the STE path re-programs by design). A
    schedule additionally column-shards the states over `model` and rides
    on the bundle for per-core ledger reporting."""
    if cell.kind == "train":
        if program is not None:
            raise ValueError("AimcProgram is a serving-only handle; "
                             "noise-aware training re-programs per step")
        return make_train_step(spec, cell, mesh, exe)
    if cell.kind == "prefill":
        return make_prefill_step(spec, cell, mesh, exe, program)
    return make_serve_step(spec, cell, mesh, exe, program)


def input_specs(spec: ArchSpec, cell: ShapeCell, mesh,
                exe: Execution = Execution(), program=None) -> tuple:
    """ShapeDtypeStruct stand-ins for every input of the cell's step function
    (weak-type-correct, shardable, zero device allocation)."""
    return make_step(spec, cell, mesh, exe, program).abstract_inputs
