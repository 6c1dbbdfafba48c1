"""Per-architecture smoke tests (assignment requirement).

Each assigned architecture instantiates its REDUCED same-family config and
runs one forward pass + one training-style grad step on CPU, asserting
output shapes and absence of NaNs. Serving paths (prefill + decode vs
full forward) are cross-validated for every family that decodes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_arch, list_archs
from repro.models.layers import Execution

EXE = Execution(compute_dtype="float32")
ARCHS = list_archs()


def _smoke_batch(spec, b=2, s=32):
    cfg = spec.smoke_cfg
    key = jax.random.PRNGKey(0)
    if spec.family == "audio":
        tgt = 16
        return {"frames": jax.random.normal(key, (b, s, cfg.d_model)),
                "tokens": jnp.ones((b, tgt), jnp.int32),
                "labels": jnp.ones((b, tgt), jnp.int32)}
    out = {"tokens": jnp.arange(b * s).reshape(b, s) % cfg.vocab,
           "labels": jnp.ones((b, s), jnp.int32)}
    if spec.family == "vlm":
        out["patch_embeds"] = jax.random.normal(
            key, (b, cfg.n_patches, cfg.d_model))
    return out


def _forward(model, spec, params, batch, rng=None):
    cfg = spec.smoke_cfg
    if spec.family == "audio":
        return model.forward(params, batch, cfg, EXE, rng, return_hidden=True)
    if spec.family == "vlm":
        return model.forward(params, batch["tokens"], cfg, EXE, rng,
                             patch_embeds=batch["patch_embeds"],
                             return_hidden=True)
    return model.forward(params, batch["tokens"], cfg, EXE, rng,
                         return_hidden=True)


@pytest.mark.parametrize("arch_id", ARCHS)
def test_forward_smoke(arch_id):
    spec = get_arch(arch_id)
    model = spec.model_module()
    cfg = spec.smoke_cfg
    params = model.init(jax.random.PRNGKey(0), cfg)
    batch = _smoke_batch(spec)
    h, aux = _forward(model, spec, params, batch)
    assert h.shape[-1] == cfg.d_model
    assert bool(jnp.all(jnp.isfinite(h)))


@pytest.mark.parametrize("arch_id", ARCHS)
def test_train_grad_smoke(arch_id):
    """One grad step: finite loss, finite nonzero grads, shapes preserved."""
    spec = get_arch(arch_id)
    model = spec.model_module()
    cfg = spec.smoke_cfg
    params = model.init(jax.random.PRNGKey(0), cfg)
    batch = _smoke_batch(spec)

    def loss_fn(p):
        h, aux = _forward(model, spec, p, batch)
        unemb = model.unembed_matrix(p, cfg)
        logits = h.astype(jnp.float32) @ unemb.astype(jnp.float32)
        labels = batch["labels"]
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(lse - gold) + 0.01 * aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss))
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0
    for p, g in zip(jax.tree.leaves(params), jax.tree.leaves(grads)):
        assert p.shape == g.shape


@pytest.mark.parametrize("arch_id", ARCHS)
def test_aimc_execution_mode(arch_id):
    """The paper's technique as an execution mode: AIMC forward stays close
    to the digital forward for every architecture family."""
    from repro.core.aimc import AimcConfig
    spec = get_arch(arch_id)
    model = spec.model_module()
    cfg = spec.smoke_cfg
    params = model.init(jax.random.PRNGKey(0), cfg)
    batch = _smoke_batch(spec)
    h_dig, _ = _forward(model, spec, params, batch)
    exe_aimc = Execution(mode="aimc", compute_dtype="float32",
                         aimc=AimcConfig(tile_rows=128, impl="ref"))
    rng = jax.random.PRNGKey(1)
    if spec.family == "audio":
        h_ana, _ = model.forward(params, batch, cfg, exe_aimc, rng,
                                 return_hidden=True)
    elif spec.family == "vlm":
        h_ana, _ = model.forward(params, batch["tokens"], cfg, exe_aimc, rng,
                                 patch_embeds=batch["patch_embeds"],
                                 return_hidden=True)
    else:
        h_ana, _ = model.forward(params, batch["tokens"], cfg, exe_aimc, rng,
                                 return_hidden=True)
    assert bool(jnp.all(jnp.isfinite(h_ana)))
    cos = jnp.sum(h_dig * h_ana) / (jnp.linalg.norm(h_dig)
                                    * jnp.linalg.norm(h_ana) + 1e-9)
    assert float(cos) > 0.9, f"AIMC forward diverged: cos={float(cos):.3f}"


# ---------------------------------------------------------------------------
# decode-vs-forward consistency (KV cache / recurrent state correctness)
# ---------------------------------------------------------------------------

def _decode_match(spec, atol, s=12):
    import dataclasses as _dc
    model = spec.model_module()
    cfg = spec.smoke_cfg
    if getattr(cfg, "n_experts", 0):
        # exact fwd/decode agreement needs drop-free routing: the capacity
        # competition differs between a 1-token decode and a full sequence
        cfg = _dc.replace(cfg, capacity_factor=float(cfg.n_experts))
    params = model.init(jax.random.PRNGKey(0), cfg)
    b = 2
    toks = (jnp.arange(b * s).reshape(b, s) * 7 + 1) % cfg.vocab

    logits_full, _ = model.forward(params, toks, cfg, EXE)

    if spec.module == "transformer":
        prefill_kwargs = {}
        if spec.family == "vlm":
            pe = jax.random.normal(jax.random.PRNGKey(1),
                                   (b, cfg.n_patches, cfg.d_model))
            logits_full, _ = model.forward(params, toks, cfg, EXE,
                                           patch_embeds=pe)
            prefill_kwargs["patch_embeds"] = pe
        _, cache = model.prefill(params, toks[:, :-1], cfg, EXE,
                                 max_seq=s, cache_dtype=jnp.float32,
                                 **prefill_kwargs)
        logits_step, _ = model.decode_step(params, cache, toks[:, -1:],
                                           cfg, EXE)
        got = logits_step[:, -1]
    else:  # recurrent: feed tokens one by one through decode_step
        cache = model.init_cache(cfg, b, s, jnp.float32)
        for t in range(s):
            logits_step, cache = model.decode_step(params, cache,
                                                   toks[:, t:t + 1], cfg, EXE)
        got = logits_step[:, -1]

    want = logits_full[:, -1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)


@pytest.mark.parametrize("arch_id", ["granite_8b", "olmoe_1b_7b",
                                     "internvl2_1b", "mellum2_12b"])
def test_transformer_decode_matches_forward(arch_id):
    _decode_match(get_arch(arch_id), atol=2e-3)


def test_xlstm_decode_matches_forward():
    """Chunkwise-parallel mLSTM == stepwise recurrence (algebraic identity)."""
    _decode_match(get_arch("xlstm_350m"), atol=5e-3, s=16)


def test_rglru_decode_matches_forward():
    _decode_match(get_arch("recurrentgemma_9b"), atol=5e-3)


def test_encdec_decode_matches_forward():
    spec = get_arch("seamless_m4t_large_v2")
    model = spec.model_module()
    cfg = spec.smoke_cfg
    params = model.init(jax.random.PRNGKey(0), cfg)
    b, src, tgt = 2, 16, 6
    frames = jax.random.normal(jax.random.PRNGKey(1), (b, src, cfg.d_model))
    toks = (jnp.arange(b * tgt).reshape(b, tgt) * 5 + 1) % cfg.vocab
    batch = {"frames": frames, "tokens": toks, "labels": toks}
    logits_full, _ = model.forward(params, batch, cfg, EXE)
    _, cache = model.prefill(params, frames, toks[:, :-1], cfg, EXE,
                             max_seq=tgt, cache_dtype=jnp.float32)
    logits_step, _ = model.decode_step(params, cache, toks[:, -1:], cfg, EXE)
    np.testing.assert_allclose(np.asarray(logits_step[:, -1]),
                               np.asarray(logits_full[:, -1]), atol=2e-3)


def test_shape_cells_defined():
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    from repro.configs import all_cells
    cells = all_cells()
    # 11 archs x 4 shapes - 9 long_500k skips (only rglru + xlstm run it)
    assert len(cells) == 35
    longs = [a for (a, s) in cells if s == "long_500k"]
    assert sorted(longs) == ["recurrentgemma_9b", "xlstm_350m"]
