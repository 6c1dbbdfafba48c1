"""End-to-end system tests: the launcher step functions executed for real on
a 1x1 CPU mesh with reduced configs — train steps run, losses fall, serving
steps produce tokens, coupling paths agree numerically."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeCell, get_arch
from repro.core.aimc import AimcConfig, program_linear
from repro.core.coupling import loose_forward, tight_forward
from repro.data.pipeline import DataConfig, host_batch
from repro.launch.mesh import make_mesh
from repro.launch.shardings import to_named
from repro.launch.steps import make_step
from repro.models.layers import Execution


def _tiny_spec(arch_id: str, **overrides):
    """An ArchSpec whose FULL config is the smoke config (CPU-runnable)."""
    spec = get_arch(arch_id)
    return dataclasses.replace(spec, model_cfg=spec.smoke_cfg, **overrides)


def _run_train(arch_id, steps=3, exec_mode="digital"):
    spec = _tiny_spec(arch_id)
    cell = ShapeCell("tiny", seq_len=32, global_batch=4, kind="train")
    mesh = make_mesh((1, 1), ("data", "model"))
    exe = (Execution(mode="aimc", aimc=AimcConfig(tile_rows=128, impl="ref"))
           if exec_mode == "aimc" else Execution())
    with jax.set_mesh(mesh):
        bundle = make_step(spec, cell, mesh, exe)
        step = jax.jit(bundle.fn,
                       in_shardings=to_named(bundle.in_shardings, mesh),
                       out_shardings=to_named(bundle.out_shardings, mesh))
        model = spec.model_module()
        params = jax.tree.map(
            lambda x: x.astype(jnp.float32),
            model.init(jax.random.PRNGKey(0), spec.smoke_cfg))
        from repro.optim import make_optimizer
        opt_state = make_optimizer(spec.optimizer)[0](params)
        cfgd = DataConfig(vocab=spec.smoke_cfg.vocab, seq_len=cell.seq_len,
                          global_batch=cell.global_batch)
        losses = []
        for i in range(steps):
            hb = host_batch(cfgd, i, 0, 1)
            batch = {"tokens": jnp.asarray(hb["tokens"]),
                     "labels": jnp.asarray(hb["labels"])}
            if spec.family == "vlm":
                batch["patch_embeds"] = jnp.zeros(
                    (cell.global_batch, spec.smoke_cfg.n_patches,
                     spec.smoke_cfg.d_model), jnp.bfloat16)
                batch["labels"] = batch["labels"].at[
                    :, :spec.smoke_cfg.n_patches].set(-1)
            rng = jnp.asarray([0, i], jnp.uint32)
            params, opt_state, metrics = step(params, opt_state, batch, rng)
            losses.append(float(metrics["loss"]))
        return losses


@pytest.mark.parametrize("arch_id", ["llama32_3b", "olmoe_1b_7b",
                                     "xlstm_350m"])
def test_train_step_runs_and_learns(arch_id):
    losses = _run_train(arch_id, steps=4)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"


def test_train_step_aimc_mode():
    """The paper's technique inside the full training loop (noise-aware)."""
    losses = _run_train("llama32_3b", steps=3, exec_mode="aimc")
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] * 1.05


def test_serve_steps_run():
    spec = _tiny_spec("granite_8b")
    cell = ShapeCell("tiny_dec", seq_len=64, global_batch=2, kind="decode")
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        bundle = make_step(spec, cell, mesh, Execution())
        step = jax.jit(bundle.fn,
                       in_shardings=to_named(bundle.in_shardings, mesh),
                       out_shardings=to_named(bundle.out_shardings, mesh))
        model = spec.model_module()
        params = model.init(jax.random.PRNGKey(0), spec.smoke_cfg)
        params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        cache = model.init_cache(spec.smoke_cfg, 2, 64, jnp.bfloat16)
        toks = jnp.ones((2, 1), jnp.int32)
        for _ in range(3):
            toks, cache = step(params, cache, toks)
        assert toks.shape == (2, 1)
        assert int(cache["len"][0]) == 3


def test_coupling_numerically_identical():
    """Tight (fused) and loose (HBM-staged) produce the same numbers —
    the coupling choice is a performance distinction, not a math one."""
    cfg = AimcConfig(tile_rows=256, impl="ref")
    w = jax.random.normal(jax.random.PRNGKey(0), (512, 128)) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 512))
    st = program_linear(w, cfg)
    y_t = tight_forward(st, x, cfg)
    y_l = loose_forward(st, x, cfg)
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_l),
                               rtol=0, atol=1e-5)


def test_elastic_reshard_roundtrip(tmp_path):
    """Checkpoint on one mesh, restore onto another (elastic rescale)."""
    from repro.checkpoint import checkpoint
    from repro.launch.shardings import get_param_specs, fit_specs
    params = {"blocks": {"wq": jnp.arange(64.0).reshape(1, 8, 8)},
              "embed": jnp.ones((16, 8))}
    checkpoint.save(str(tmp_path), 5, params)
    mesh2 = make_mesh((1, 1), ("data", "model"))  # CPU: same shape, new mesh
    specs = fit_specs(get_param_specs(params, mesh2), params, mesh2)
    step, restored, _ = checkpoint.restore_latest(str(tmp_path), params,
                                                  mesh2, specs)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["blocks"]["wq"]),
                                  np.asarray(params["blocks"]["wq"]))
