"""Sparse-expert serving and the per-layer attention kinds (mellum2_12b).

* The served math (`transformer.prefill`, then cached `decode_step`, as the
  engine's closures run them) agrees with the plain reference
  (`bench/references/mellum2_aimc.py`) at a small size: 4 layers (three
  sliding over a window of 8, one full with YaRN rope), sequences of 24,
  8 experts at top-2, the programmed crossbar path under a fixed DAC scale
  (so every call quantises as the reference's does). The reference without
  the window does not agree: the window binds.
* Dropless routing: a request's logits served alone and amid 7 batch mates
  agree to float32 rounding; capacity dispatch (training's) does not.
* The grouped expert kernel matches its oracle in interpret mode, with
  empty experts and uneven groups, and each expert's rows come out as a
  call of `aimc_apply` over just those rows gives them.
* The engine counts the experts each decode step touches.
* On a mesh with a `model` axis, digital experts keep the expert-parallel
  capacity dispatch: a compiled decode step on a forced 4-device host mesh
  gathers no expert stack.
* The model's YaRN table and the reference's, written apart, agree.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.check import dims_of
from bench.references import mellum2_aimc as ref
from repro.configs import get_arch
from repro.core.aimc import (AimcConfig, aimc_apply, aimc_apply_experts,
                             program_stacked)
from repro.core.program import MappingPlan, program_model
from repro.kernels import ops
from repro.models import moe as moe_lib
from repro.models.layers import Execution, rope_params
from repro.runtime.batcher import Request
from repro.runtime.engine import ServeEngine

DIGITAL = Execution(compute_dtype="float32")
SEQ, PROMPT = 24, 10
DAC = 0.02            # a fixed DAC scale, in the reference and the program


@pytest.fixture(scope="module")
def mellum():
    spec = get_arch("mellum2_12b")
    model = spec.model_module()
    key = jax.random.PRNGKey(3)
    return spec, spec.smoke_cfg, model, key, model.init(key, spec.smoke_cfg)


def _programmed(cfg_params, tile_rows=64, input_scale=DAC, impl="ref"):
    aimc = AimcConfig(tile_rows=tile_rows, impl=impl, input_scale=input_scale)
    exe = Execution(mode="aimc", compute_dtype="float32", aimc=aimc,
                    programmed=True)
    program = program_model(cfg_params, MappingPlan(), aimc)
    return exe, program, program.install(cfg_params)


def _served_logits(model, cfg, exe, params, tokens):
    """[T - PROMPT + 1, V] logits of row 0: the prefill's at the last prompt
    position, then one cached decode step per later token."""
    logits, cache = model.prefill(params, tokens[:, :PROMPT], cfg, exe,
                                  max_seq=SEQ, cache_dtype=jnp.float32)
    out = [logits[0, 0]]
    for t in range(PROMPT, tokens.shape[1] - 1):
        logits, cache = model.decode_step(params, cache, tokens[:, t:t + 1],
                                          cfg, exe, ragged=True)
        out.append(logits[0, 0])
    return np.stack(out)


@pytest.mark.parametrize("window,agrees", [(8, True), (0, False)])
def test_prefill_then_decode_matches_reference(mellum, window, agrees):
    spec, cfg, model, key, params = mellum
    assert cfg.layer_types[:4] == ("sliding_attention",) * 3 + (
        "full_attention",)
    exe, _, installed = _programmed(params)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab, size=(1, SEQ)), jnp.int32)
    got = _served_logits(model, cfg, exe, installed, tokens)
    conf = {"execution": {"aimc": {"tile_rows": 64, "adc_alpha": 1.0,
                                   "input_scale": 0.0}}}
    cands = jnp.broadcast_to(jnp.arange(cfg.vocab)[:, None, None],
                             (cfg.vocab, 1, SEQ))
    _, picked, _ = ref.forward_scores(
        dims_of(cfg, conf), key, tokens, jnp.ones((1, SEQ), bool), cands,
        sliding_window=window, n_experts=cfg.n_experts, top_k=cfg.top_k,
        dac_scale=DAC)
    want = np.asarray(picked[:, 0, PROMPT - 1:SEQ - 1]).T
    err = np.abs(got - want).max()
    if agrees:
        assert err < 1e-4, err
    else:
        assert err > 1e-2, err


def _alone_and_amid(model, cfg, params, monkeypatch, dispatch):
    """Row 0 served alone, and the same request in the last of 8 lanes
    whose other 7 hold copies of it (every lane routes to the same experts,
    and the last lane's rows come last in each expert's queue)."""
    if dispatch == "capacity":
        def capacity(x, router_w, w_gate, w_up, w_down, *, top_k, exe,
                     layer=None):
            y, _ = moe_lib.moe_ffn(x, router_w, w_gate, w_up, w_down,
                                   top_k=top_k, exe=exe,
                                   capacity_factor=cfg.capacity_factor)
            return y, jnp.int32(0)
        monkeypatch.setattr(moe_lib, "moe_serve", capacity)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, cfg.vocab, size=(1, SEQ)), jnp.int32)
    alone = _served_logits(model, cfg, DIGITAL, params, tokens)
    batch = jnp.concatenate([tokens] * 8)
    logits, cache = model.prefill(params, batch[:, :PROMPT], cfg, DIGITAL,
                                  max_seq=SEQ, cache_dtype=jnp.float32)
    amid = [logits[-1, 0]]
    for t in range(PROMPT, SEQ - 1):
        logits, cache = model.decode_step(params, cache, batch[:, t:t + 1],
                                          cfg, DIGITAL, ragged=True)
        amid.append(logits[-1, 0])
    return alone, np.stack(amid)


@pytest.mark.parametrize("dispatch", ["dropless", "capacity"])
def test_request_logits_independent_of_batch_mates(mellum, monkeypatch,
                                                   dispatch):
    _, cfg, model, _, params = mellum
    alone, amid = _alone_and_amid(model, cfg, params, monkeypatch, dispatch)
    err = np.abs(alone - amid).max()
    if dispatch == "dropless":
        assert err < 1e-5, err
    else:       # capacity drops the last lane's rows: the test has teeth
        assert err > 1e-2, err


def _grouped_case(sizes, bm, key):
    """Rows sorted by expert in whole blocks of ``bm`` for group sizes
    ``sizes``, and the layout's per-block expert ids."""
    n_blocks = sum(-(-s // bm) for s in sizes)
    total = -(-(sum(sizes) + len(sizes) * (bm - 1)) // bm)
    blocks, rows = [], []
    k = 200
    x = jax.random.normal(key, (total * bm, k), jnp.float32)
    for e, s in enumerate(sizes):
        nb = -(-s // bm)
        blocks += [e] * nb
        mask = np.zeros(nb * bm, bool)
        mask[:s] = True
        rows.append(mask)
    last = blocks[-1]
    blocks += [last] * (total - n_blocks)
    live = np.concatenate(rows + [np.zeros((total - n_blocks) * bm, bool)])
    x = jnp.where(jnp.asarray(live)[:, None], x, 0.0)
    return x, jnp.asarray(blocks, jnp.int32), n_blocks, live


@pytest.mark.parametrize("sizes,bm", [((5, 0, 17, 1, 0, 9), 8),
                                      ((0, 0, 3, 0, 0, 0), 8),
                                      ((30, 2, 0, 0, 41, 8), 16)])
def test_grouped_kernel_matches_oracle(sizes, bm):
    e, k, n = len(sizes), 200, 136
    key = jax.random.PRNGKey(7)
    w = jax.random.normal(key, (2, 2, e, k, n), jnp.float32) * 0.1
    aimc = AimcConfig(tile_rows=128, impl="ref")
    gate, up = (program_stacked(w[g], aimc) for g in range(2))
    x, blocks, n_used, live = _grouped_case(sizes, bm,
                                            jax.random.PRNGKey(8))
    layer = jnp.int32(1)
    s_x = jax.random.uniform(jax.random.PRNGKey(9), (e,), minval=0.01,
                             maxval=0.03)
    xp = jnp.pad(x, ((0, 0), (0, 256 - k)))
    args = (xp, (gate.w_q, up.w_q), (gate.s_w, up.s_w), s_x, layer, blocks,
            jnp.int32(n_used))
    want = ops.aimc_matmul_experts(*args, adc_step=aimc.adc_step,
                                   block_rows=bm, impl="ref")
    got = ops.aimc_matmul_experts(*args, adc_step=aimc.adc_step,
                                  block_rows=bm, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    assert not np.asarray(got)[:, n_used * bm:].any()
    # each expert's rows as one `aimc_apply` over just those rows gives them
    y = aimc_apply_experts((gate, up), x, layer, blocks, n_used, aimc,
                           block_rows=bm)
    row_expert = np.repeat(np.asarray(blocks), bm)
    for ex in range(e):
        rows = np.flatnonzero(live & (row_expert == ex))
        if not rows.size:
            continue
        for g, st in enumerate((gate, up)):
            one = jax.tree.map(lambda a: a[1, ex], st)
            alone = aimc_apply(one, x[rows], aimc)
            np.testing.assert_allclose(np.asarray(y[g, rows]),
                                       np.asarray(alone), atol=1e-5,
                                       rtol=1e-5)


def test_engine_counts_experts_touched(mellum):
    spec, cfg, model, _, params = mellum
    exe, program, installed = _programmed(params, input_scale=0.0)
    eng = ServeEngine(model, cfg, exe, installed, n_slots=4, prompt_pad=16,
                      max_seq=SEQ, family=spec.family, module=spec.module,
                      program=program, decode_chunk=4)
    eng.warmup()
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=list(rng.integers(1, cfg.vocab, size=6)),
                    max_new=5, arrival=0.0) for i in range(6)]
    report = eng.serve(reqs)
    layer_steps = report.n_steps * cfg.n_layers
    # every step routes 4 lanes x top-2 rows: 2 to 8 distinct experts
    assert report.n_steps > 0
    assert cfg.top_k * layer_steps <= report.experts_touched
    assert report.experts_touched <= cfg.n_experts * layer_steps
    assert all(len(r.tokens) == 5 for r in report.records.values())


def test_lowered_closures_name_the_moe_scopes(mellum):
    """The engine's decode and prefill closures carry the sparse-expert
    block's scopes (`tracing.MOE_SCOPES`) in their ops' names."""
    import re

    from repro import tracing
    spec, cfg, model, _, params = mellum
    exe, program, installed = _programmed(params, input_scale=0.0)
    eng = ServeEngine(model, cfg, exe, installed, n_slots=2, prompt_pad=8,
                      max_seq=SEQ, family=spec.family, module=spec.module,
                      program=program)
    sess = eng.begin()
    tokens, valid = eng._pad_prompt([3, 1, 4])
    for low in (eng._decode_jits[1].lower(eng.params, sess.cache,
                                          sess.tok_buf, sess.state),
                eng._jit_prefill.lower(eng.params, tokens, valid)):
        names = re.findall(r'loc\("([^"]*)"', low.as_text(debug_info=True))
        parts = {p for name in names for p in name.split("/")}
        assert set(tracing.MOE_SCOPES) | {tracing.BLOCK,
                                          tracing.AIMC_MVM} <= parts


def test_dense_engine_counts_no_experts():
    spec = get_arch("granite_8b")
    model = spec.model_module()
    params = model.init(jax.random.PRNGKey(0), spec.smoke_cfg)
    eng = ServeEngine(model, spec.smoke_cfg, DIGITAL, params, n_slots=2,
                      prompt_pad=8, max_seq=16)
    report = eng.serve([Request(rid=0, prompt=[1, 2, 3], max_new=4,
                                arrival=0.0)])
    assert report.n_steps > 0 and report.experts_touched == 0


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_rope_tables_agree_with_reference(kind):
    section = dict(get_arch("mellum2_12b").model_cfg.rope_parameters)[kind]
    inv, scale = rope_params(128, dict(section))
    want_inv, want_scale = ref.rope_table(128, ref.ROPE[kind])
    np.testing.assert_allclose(inv, want_inv, rtol=1e-6)
    assert scale == pytest.approx(want_scale)
    plain = 1.0 / 5e5 ** (np.arange(0, 128, 2) / 128)
    if kind == "full_attention":
        # YaRN keeps the fastest dims and divides the slowest by the factor
        assert inv[0] == pytest.approx(plain[0], rel=1e-6)
        assert inv[-1] == pytest.approx(plain[-1] / 16, rel=1e-6)
        assert scale == pytest.approx(0.1 * np.log(16) + 1)
    else:
        np.testing.assert_allclose(inv, plain, rtol=1e-6)
        assert scale == 1.0


def test_published_config_is_hashable_and_patterned():
    cfg = get_arch("mellum2_12b").model_cfg
    assert hash(cfg) == hash(dataclasses.replace(cfg))
    assert cfg.layer_types.count("full_attention") == 7
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff) == (64, 8, 896)
    # a JSON dict of rope sections normalises to the same config
    again = dataclasses.replace(
        cfg, rope_parameters={k: dict(v) for k, v in cfg.rope_parameters},
        layer_types=list(cfg.layer_types))
    assert again == cfg


def test_expert_block_rows():
    assert moe_lib.expert_block_rows(32 * 8, 64) == 8       # a decode step
    assert moe_lib.expert_block_rows(1536 * 8, 64) == 128   # a prefill
    assert moe_lib.expert_block_rows(10, 8) == 8


@pytest.mark.slow
def test_sharded_moe_decode_gathers_no_expert_stack():
    """olmoe's digital decode step on a (data 2, model 2) mesh of forced
    host devices, its expert stacks split over `model`: serving keeps the
    expert-parallel dispatch (`moe.serves_dropless` is False there), and no
    collective of the compiled step carries a whole expert stack."""
    code = textwrap.dedent("""
        import os, re
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=4 "
            + os.environ.get("XLA_FLAGS", ""))
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.launch.mesh import make_mesh
        from repro.models.moe import serves_dropless
        from repro.models.layers import Execution
        assert jax.device_count() == 4, jax.devices()
        spec = get_arch("olmoe_1b_7b")
        cfg, model = spec.smoke_cfg, spec.model_module()
        exe = Execution(compute_dtype="float32")
        params = model.init(jax.random.PRNGKey(0), cfg)
        cache = model.init_cache(cfg, 4, 16, jnp.float32)
        mesh = make_mesh((2, 2), ("data", "model"))
        assert serves_dropless(params["blocks"]["we_gate"], exe)
        assert not serves_dropless(params["blocks"]["we_gate"], exe, mesh)

        def place(path, x):
            name = jax.tree_util.keystr(path)
            split = any(n in name for n in ("we_gate", "we_up", "we_down"))
            return NamedSharding(mesh, P(None, "model") if split else P())

        with jax.set_mesh(mesh):
            params = jax.device_put(
                params, jax.tree_util.tree_map_with_path(place, params))
            step = jax.jit(lambda p, c, t: model.decode_step(p, c, t, cfg,
                                                             exe))
            hlo = step.lower(params, cache, jnp.ones((4, 1), jnp.int32)
                             ).compile().as_text()
        e, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
        stacks = {(e, d, ff), (e, ff, d)}
        shapes = re.findall(
            r"= \\w+\\[([\\d,]*)\\][^ ]* (?:all-gather|all-reduce|"
            r"all-to-all|collective-permute)\\(", hlo)
        assert shapes, "no collectives: the step was not sharded"
        for shape in shapes:
            dims = tuple(int(v) for v in shape.split(",") if v)
            assert dims[-3:] not in stacks, dims
        print("NO_EXPERT_GATHER_OK", len(shapes))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        ["src", env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "NO_EXPERT_GATHER_OK" in proc.stdout
