"""The comparison that decides ``correct``, at smoke size on the CPU.

* The plain reference computes what the model computes: on one sequence,
  where the served path and the reference quantise the same rows together,
  the reference ranks the model's own argmax first everywhere; and on a
  padded prompt, as the engine's prefill quantises it, it ranks the engine's
  first token first.
* Whole runs of each cell (`bench.run.execute`, the harness's look for a
  chip skipped) with the timed path broken underneath come out not correct:
  a decode step that hands back its cache unchanged, a token altered where
  the decode step produces it, and a prefill whose first token is altered.
* Each control of the configuration -- the reference at int4 crossbar codes,
  and the reference with its float32 parts in bfloat16 -- put in the
  program's place comes out not correct through the same judgement.

Smoke size is `bench.system.transformer_config(smoke=True)`: 2 layers of
width 512, vocab 2048, a 4-slot engine. The limits here are set from smoke
readings on the CPU (``SMOKE_LIMITS``), not from the cells' own.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, system
from bench import run as bench_run
from bench.references import decoder_aimc as ref
from bench.rehearse import smoke_kwargs
from bench.seeds import seed_key

CELLS = ["granite8b-aimc.batch", "glm4-9b-aimc.online"]
SEED = 2**33 + 11


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    """Tests compile in memory: the persistent cache stays off."""
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: None)


@pytest.mark.parametrize("config", ["granite8b-aimc", "glm4-9b-aimc"])
def test_reference_ranks_the_models_argmax_first(config):
    from repro.core.aimc import AimcConfig
    from repro.core.program import MappingPlan, program_model
    from repro.models.layers import Execution

    conf, model, cfg, installed, exe, key = _smoke_model(config)
    toks = np.random.default_rng(0).integers(1, cfg.vocab, (1, 40),
                                             dtype=np.int32)
    logits, _ = model.forward(installed, jnp.asarray(toks), cfg, exe)
    top = np.argmax(np.asarray(logits), -1).astype(np.int32)
    best, picked, ref_top = ref.forward_scores(
        check.dims_of(cfg, conf), key, jnp.asarray(toks),
        jnp.ones((1, 40), bool), jnp.asarray(top[None]))
    assert np.array_equal(np.asarray(ref_top), top)
    assert float(np.max(np.asarray(best) - np.asarray(picked[0]))) == 0.0


@pytest.mark.parametrize("config", ["granite8b-aimc", "glm4-9b-aimc"])
def test_reference_ranks_the_prefills_first_token_first(config):
    conf, model, cfg, installed, exe, key = _smoke_model(config)
    pad, n = 32, 21
    prompt = np.random.default_rng(1).integers(1, cfg.vocab, n,
                                               dtype=np.int32)
    tokens = np.zeros((1, pad), np.int32)
    tokens[0, :n] = prompt
    logits, _ = model.prefill(installed, jnp.asarray(tokens), cfg, exe,
                              valid_len=jnp.asarray([n], jnp.int32))
    first = int(np.argmax(np.asarray(logits)[0, -1]))
    served = {0: (tuple(int(t) for t in prompt), [first], 1)}
    t, v, s = check.prefills(served, [0], pad)
    best, picked, ref_top = ref.forward_scores(
        check.dims_of(cfg, conf), key, jnp.asarray(t), jnp.asarray(v),
        jnp.asarray(np.where(s >= 0, s, 0)[None]))
    assert int(np.asarray(ref_top)[0, n - 1]) == first
    assert float(np.asarray(best)[0, n - 1]
                 - np.asarray(picked)[0, 0, n - 1]) == 0.0


def _smoke_model(config):
    from repro.core.program import MappingPlan, program_model
    conf = system.load_config(config)
    spec, cfg = system.transformer_config(conf, smoke=True)
    model = spec.model_module()
    key = seed_key(SEED)
    params = system.init_params(model, cfg, key, conf)
    exe = system.execution(conf)
    installed = program_model(params, MappingPlan(), exe.aimc).install(
        params)
    return conf, model, cfg, installed, exe, key


def _run(cell, monkeypatch, after_build=None, controls=()):
    conf = system.load_config(bench_run.cell_of(bench_run.load_benchmark(),
                                                cell)["config"])
    conf["check"] = dict(conf["check"], limits=dict(SMOKE_LIMITS))
    monkeypatch.setattr(system, "load_config", lambda name: conf)
    bench = bench_run.load_benchmark()
    return bench_run.execute(cell, SEED, 1.0, False, bench=bench,
                             after_build=after_build, controls=controls,
                             **smoke_kwargs(cell, bench))


SMOKE_LIMITS = {"gap_mean": 0.08, "first_gap_mean": 0.002}


class _AlteredPrefill:
    """The engine's prefill closure with its first token altered."""

    def __init__(self, jitted, vocab):
        self.jitted, self.vocab = jitted, vocab

    def __call__(self, params, tokens, valid_len):
        tok, cache = self.jitted(params, tokens, valid_len)
        return (tok + 1) % self.vocab, cache

    def _cache_size(self):
        return self.jitted._cache_size()


def _broken(fault):
    def after_build(sut):
        eng = sut.engine
        if fault == "first_token_altered":
            eng._jit_prefill = _AlteredPrefill(eng._jit_prefill,
                                               sut.cfg.vocab)
            return
        for n, f in list(eng._safe_decodes.items()):
            def broken(params, cache, tok_buf, state, f=f):
                tok_out, cache_out, state_out, ys = f(params, cache,
                                                      tok_buf, state)
                if fault == "state_unchanged":
                    return tok_out, cache, state_out, ys
                vocab = sut.cfg.vocab
                toks, act, reason = ys
                return ((tok_out + 1) % vocab, cache_out, state_out,
                        ((toks + 1) % vocab, act, reason))
            eng._safe_decodes[n] = broken
    return after_build


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, monkeypatch):
    res = _run(cell, monkeypatch)
    assert res["limits"]["short"]["value"] == 0
    assert res["correct"], res["limits"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered",
                                   "first_token_altered"])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    res = _run(cell, monkeypatch, after_build=_broken(fault))
    assert not res["correct"], res["limits"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_above_the_limit(cell, monkeypatch):
    controls = tuple(system.load_config(bench_run.cell_of(
        bench_run.load_benchmark(), cell)["config"])["check"]["controls"])
    assert set(controls) == {"int4", "bf16"}
    res = _run(cell, monkeypatch, controls=controls)
    assert res["correct"], res["limits"]
    for name in controls:
        v = res["controls"][name]
        assert not v["correct"], (name, v["limits"])


def test_sample_keeps_the_longest():
    served = {i: ((1, 2), [5] * (i % 7 + 1), 9) for i in range(40)}
    rids = check.sample(served, 3, 8)
    assert len(set(rids)) == 8
    assert rids[0] == max(served, key=lambda r: (len(served[r][1]), -r))
    assert check.sample(served, 3, 8) == rids


def test_short_requests():
    served = {0: ((1,) * 10, [1] * 5, 5), 1: ((1,) * 10, [1] * 4, 5),
              2: ((1,) * 60, [1] * 5, 10)}      # the cache (64 rows) caps 2
    assert check.short_requests(served, max_seq=64) == 1
