"""Chunked (k-step scanned) decode equivalence suite (runtime/engine.py,
DESIGN.md §13).

The contracts pinned here (ISSUE 7 acceptance criteria):
  * decode is BIT-EQUAL across ``decode_chunk`` sizes (k in {1, 4, 8}) and
    against the legacy per-step loop's oracle (`static_generate`) — single
    device in-process, forced 2-device data/model meshes in a subprocess;
  * mid-chunk retirement works: an EOS inside a chunk frees the slot for
    the next admission, and the freed lane stays bit-frozen;
  * per-chunk ledger exactness: after EVERY `step()` the device-side
    observed vectors equal the per-request books and reconcile exactly
    against ``program.mvm_counts()`` — not just at end of trace;
  * EOS tokens are control, not payload: they never appear in delivered
    ``tokens`` but their decode vectors stay in the CM_* books, and an
    EOS-heavy trace still reconciles exactly;
  * the decode step performs NO host->device transfer (the active mask
    lives on device — the PR-7 fix for the per-step `jnp.asarray(active)`
    rebuild), enforced with a transfer guard;
  * `transformer.decode_step(active=...)`, which drops frozen lanes' K/V
    rows in place, is bit-equal to a decode step followed by a
    `mask_batch_select` of the whole cache.
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

from repro.configs import get_arch
from repro.core.aimc import AimcConfig
from repro.core.program import MappingPlan, program_model
from repro.models.layers import Execution, mask_batch_select
from repro.runtime.batcher import (Request, poisson_trace, reconcile,
                                   synchronized_trace)
from repro.runtime.engine import ServeEngine, static_generate

EXE = Execution(compute_dtype="float32")


@pytest.fixture(scope="module")
def tfm():
    spec = get_arch("granite-8b")
    cfg = spec.smoke_cfg
    model = spec.model_module()
    params = model.init(jax.random.PRNGKey(0), cfg)
    return spec, cfg, model, params


def make_engine(tfm, **kw):
    spec, cfg, model, params = tfm
    kw.setdefault("n_slots", 3)
    kw.setdefault("prompt_pad", 8)
    kw.setdefault("max_seq", 24)
    kw.setdefault("family", spec.family)
    kw.setdefault("module", spec.module)
    return ServeEngine(model, cfg, kw.pop("exe", EXE),
                       kw.pop("params", params), **kw)


def _programmed(tfm, **kw):
    spec, cfg, model, params = tfm
    aimc = AimcConfig(impl="ref", input_scale=0.1)
    exe = Execution(mode="aimc", aimc=aimc, compute_dtype="float32",
                    programmed=True)
    program = program_model(params, MappingPlan(), aimc,
                            jax.random.PRNGKey(3))
    eng = make_engine(tfm, exe=exe, params=program.install(params),
                      program=program, **kw)
    return eng, program


# ---------------------------------------------------------------------------
# bit-equality across chunk sizes
# ---------------------------------------------------------------------------

def test_chunked_decode_bit_equal_across_k_on_ragged_trace(tfm):
    spec, cfg, model, params = tfm
    reqs = poisson_trace(8, rate=300.0, seed=9, prompt_len=(3, 8),
                         max_new=(1, 9), vocab=cfg.vocab)
    base = make_engine(tfm, n_slots=3, decode_chunk=1)
    base.warmup()
    ref = base.serve(list(reqs))
    for k in (4, 8):
        eng = make_engine(tfm, n_slots=3, decode_chunk=k)
        eng.warmup()
        rep = eng.serve(list(reqs))
        for r in reqs:
            assert rep.tokens(r.rid) == ref.tokens(r.rid), (k, r.rid)
            assert (rep.records[r.rid].finish_reason
                    == ref.records[r.rid].finish_reason), (k, r.rid)
            assert (rep.records[r.rid].decode_vectors
                    == ref.records[r.rid].decode_vectors), (k, r.rid)
        # chunking changes host scheduling, never the books
        assert rep.observed_vectors == rep.useful_vectors, k
        assert rep.generated_tokens == ref.generated_tokens, k
        # serving the ragged trace never recompiled anything: decode holds
        # exactly one executable per ladder length, all built at warmup
        assert eng.compile_counts() == {"prefill": 1, "insert": 1,
                                        "decode": len(eng._ladder)}, k


def test_chunked_sync_trace_bit_equal_static(tfm):
    spec, cfg, model, params = tfm
    reqs = synchronized_trace(3, prompt_len=8, max_new=6, seed=1,
                              vocab=cfg.vocab)
    prompts = jnp.asarray([r.prompt for r in reqs], jnp.int32)
    gen, _ = static_generate(model, cfg, EXE, params, prompts, 6, max_seq=24)
    for k in (4, 8):
        eng = make_engine(tfm, n_slots=3, decode_chunk=k)
        eng.warmup()
        report = eng.serve(list(reqs))
        for r in reqs:
            assert report.tokens(r.rid) == [int(t) for t in gen[r.rid]], \
                f"chunk {k}: req {r.rid} diverged from the static oracle"


# ---------------------------------------------------------------------------
# mid-chunk retirement frees the slot
# ---------------------------------------------------------------------------

def test_mid_chunk_eos_retirement_frees_slot_for_next_admit(tfm):
    base = make_engine(tfm, n_slots=1, decode_chunk=1)
    base.warmup()
    # ref[0] comes from the prefill, ref[i] from decode step i. The EOS must
    # FIRST appear at decode step 2 — INSIDE a k=4 chunk — so take the first
    # prompt whose free run has a token there that it has not emitted yet.
    for start in range(1, 64):
        req = Request(rid=0, prompt=tuple(range(start, start + 8)), max_new=8)
        ref = base.serve([req]).tokens(0)
        if ref[2] not in ref[:2]:
            break
    else:
        pytest.fail("no prompt emits a fresh token at decode step 2")
    assert len(ref) == 8
    eos = ref[2]
    eng = make_engine(tfm, n_slots=1, decode_chunk=4, eos_id=eos)
    eng.warmup()
    # two identical-prompt requests through ONE slot: the second can only
    # be served if the mid-chunk retirement released the lane
    reqs = [req, Request(rid=1, prompt=req.prompt, max_new=8)]
    report = eng.serve(reqs)
    assert len(report.records) == 2
    for rid in (0, 1):
        rec = report.records[rid]
        assert rec.finish_reason == "eos", rid
        assert rec.tokens == ref[:2], rid    # EOS excluded from payload
        assert rec.decode_vectors == 2, rid  # ... but in the vector books
    assert report.observed_vectors == report.useful_vectors


# ---------------------------------------------------------------------------
# per-chunk ledger exactness (session primitives, chunk boundaries)
# ---------------------------------------------------------------------------

def test_ledgers_exact_at_every_chunk_boundary(tfm):
    eng, program = _programmed(tfm, n_slots=2, decode_chunk=4, max_seq=20)
    eng.warmup()
    sess = eng.begin()
    now = 0.0
    reqs = poisson_trace(5, rate=1000.0, seed=4, prompt_len=(3, 8),
                         max_new=(2, 7), vocab=tfm[1].vocab)
    queue = list(reqs)
    per_vec = program.mvm_counts()
    chunks = 0
    while queue or sess.slots.n_busy:
        while sess.slots.n_free and queue:
            now = eng.admit(sess, queue.pop(0), now)
        if not sess.slots.n_busy:
            break
        now = eng.step(sess, now)
        chunks += 1
        # the books must close at EVERY chunk boundary, mid-flight records
        # included — not just after the trace drains
        rep = sess.report
        assert rep.observed_vectors == sum(
            r.vectors for r in rep.records.values()), chunks
        led_sum, static = reconcile(program, rep.records,
                                    rep.observed_vectors)
        assert led_sum == static, chunks
        assert static == per_vec.scaled(rep.observed_vectors), chunks
    report = eng.finish(sess, now)
    assert chunks >= 2                       # the loop actually chunked
    # <= k steps per chunk: the while_loop exits early once every lane
    # retires, and every executed step carries >= 1 busy lane
    assert chunks <= report.n_steps <= chunks * 4
    assert report.observed_vectors >= report.n_steps
    assert report.observed_vectors == report.useful_vectors


# ---------------------------------------------------------------------------
# EOS accounting (control, not payload) on an EOS-heavy trace
# ---------------------------------------------------------------------------

def test_eos_heavy_trace_reconciles_and_excludes_eos_payload(tfm):
    spec, cfg, model, params = tfm
    reqs = poisson_trace(8, rate=400.0, seed=11, prompt_len=(3, 8),
                         max_new=(2, 8), vocab=cfg.vocab)
    probe = make_engine(tfm, n_slots=3)
    probe.warmup()
    free_run = probe.serve(list(reqs))
    # pick the most frequent emitted token -> an EOS that fires a lot
    counts = {}
    for rec in free_run.records.values():
        for t in rec.tokens:
            counts[t] = counts.get(t, 0) + 1
    eos = max(counts, key=counts.get)
    eng, program = _programmed(tfm, n_slots=3, decode_chunk=4, eos_id=eos,
                               max_seq=24)
    eng.warmup()
    report = eng.serve(list(reqs))
    assert any(r.finish_reason == "eos" for r in report.records.values()), \
        "trace was not EOS-heavy; pick a different seed"
    for rid, rec in report.records.items():
        assert eos not in rec.tokens, rid    # EOS never delivered
        if rec.finish_reason == "eos":
            # The EOS ride is booked as a vector even though no token
            # lands: a decode-step EOS after n delivered tokens rode n
            # steps. A prefill token that IS the EOS retires the request at
            # admission, as the engine books it: nothing delivered, no
            # decode step ridden, only the prompt's prefill vectors.
            assert rec.decode_vectors == len(rec.tokens), rid
            if not rec.tokens:
                assert rec.prefill_vectors == len(reqs[rid].prompt), rid
    # both countings agree, and close exactly against the program
    assert report.observed_vectors == report.useful_vectors
    led_sum, static = reconcile(program, report.records,
                                report.observed_vectors)
    assert led_sum == static


# ---------------------------------------------------------------------------
# no per-step host->device transfer (the mask lives on device)
# ---------------------------------------------------------------------------

def test_decode_step_performs_no_host_to_device_transfer(tfm):
    eng = make_engine(tfm, n_slots=2, decode_chunk=2)
    eng.warmup()
    sess = eng.begin()
    now = eng.admit(sess, Request(rid=0, prompt=(1, 2, 3), max_new=9), now=0.0)
    now = eng.step(sess, now)    # post-warmup steady state
    # the PR-4 loop rebuilt the active mask with jnp.asarray(list) every
    # step — an h2d transfer per token. The chunked loop keeps the mask in
    # device state, so a steady-state step must not transfer ANYTHING to
    # the device (readback of ys is d2h and stays allowed).
    with jax.transfer_guard_host_to_device("disallow"):
        now = eng.step(sess, now)
        now = eng.step(sess, now)
    eng.cancel_active(sess, now)
    eng.finish(sess, now)


# ---------------------------------------------------------------------------
# lane-masked K/V row writes == decode step + whole-cache select
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qkv_bias", [False, True],
                         ids=["granite", "qkv_bias"])
@pytest.mark.parametrize("ragged", [True, False], ids=["ragged", "lockstep"])
def test_masked_row_write_bit_equal_to_cache_select(tfm, ragged, qkv_bias):
    spec, cfg, model, params = tfm
    if qkv_bias:             # glm-like: q/k/v projections carry a bias
        cfg = dataclasses.replace(cfg, qkv_bias=True)
        params = model.init(jax.random.PRNGKey(1), cfg)
    b, max_seq, n_steps = 4, 12, 5
    kk, kv, kt = jax.random.split(jax.random.PRNGKey(7), 3)
    shape = (cfg.n_layers, b, max_seq, cfg.n_kv_heads, cfg.hd)
    # lane 2 sits at the max_seq cap, lane 3 reaches it in the first step
    lens = [3, 7, max_seq, max_seq - 1] if ragged else [max_seq - 3] * b
    cache = {"k": jax.random.normal(kk, shape, jnp.float32),
             "v": jax.random.normal(kv, shape, jnp.float32),
             "len": jnp.asarray(lens, jnp.int32)}
    rng = np.random.RandomState(11)
    masks = rng.rand(n_steps, b) < 0.6
    masks[0] = [True, False, True, True]
    assert masks.any(0).all() and not masks.all(0).any()
    tokens = jax.random.randint(kt, (n_steps, b, 1), 0, cfg.vocab)

    @jax.jit
    def select_step(cache, tok, active):
        logits, new = model.decode_step(params, cache, tok, cfg, EXE,
                                        ragged=ragged)
        return logits, {k: mask_batch_select(new[k], cache[k], active,
                                             axis=0 if k == "len" else 1)
                        for k in new}

    @jax.jit
    def masked_step(cache, tok, active):
        return model.decode_step(params, cache, tok, cfg, EXE,
                                 ragged=ragged, active=active)

    old = new = cache
    for t in range(n_steps):
        active = jnp.asarray(masks[t])
        logits_o, old = select_step(old, tokens[t], active)
        prev = new
        logits_n, new = masked_step(new, tokens[t], active)
        assert np.array_equal(logits_n, logits_o), t
        for key in ("k", "v", "len"):
            assert np.array_equal(new[key], old[key]), (t, key)
        idle = ~masks[t]
        for key in ("k", "v"):
            assert np.array_equal(new[key][:, idle], prev[key][:, idle])
        assert np.array_equal(new["len"], prev["len"] + masks[t])


# ---------------------------------------------------------------------------
# forced 2-device meshes: chunked decode bit-equal to single-device
# (subprocess — XLA's device count is fixed at backend init)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_chunked_sharded_bit_equal_across_two_devices():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=2 "
            + os.environ.get("XLA_FLAGS", ""))
        import jax, jax.numpy as jnp
        assert jax.device_count() == 2, jax.devices()
        from repro.configs import get_arch
        from repro.core.aimc import AimcConfig
        from repro.core.program import MappingPlan, program_model
        from repro.launch.mesh import make_mesh
        from repro.models.layers import Execution, mask_batch_select
        from repro.runtime.batcher import reconcile, synchronized_trace
        from repro.runtime.engine import ServeEngine, ShardedServeEngine

        spec = get_arch("granite-8b"); cfg = spec.smoke_cfg
        model = spec.model_module()
        params = model.init(jax.random.PRNGKey(0), cfg)
        aimc = AimcConfig(impl="ref", input_scale=0.1)
        exe = Execution(mode="aimc", aimc=aimc, compute_dtype="float32",
                        programmed=True)
        prog = program_model(params, MappingPlan(n_contexts=2), aimc,
                             jax.random.PRNGKey(2))
        params = prog.install(params)
        kw = dict(n_slots=2, prompt_pad=8, max_seq=20, family=spec.family,
                  module=spec.module, cache_dtype=jnp.float32, program=prog)
        reqs = synchronized_trace(4, prompt_len=8, max_new=6, seed=1,
                                  vocab=cfg.vocab)
        e1 = ServeEngine(model, cfg, exe, params, **kw); e1.warmup()
        ref = e1.serve(list(reqs))
        for shape in ((2, 1), (1, 2)):       # slots/data, bit lines/model
            mesh = make_mesh(shape, ("data", "model"))
            for k, n_exec in ((1, 1), (4, 3), (8, 4)):
                e2 = ShardedServeEngine(model, cfg, exe, params, mesh=mesh,
                                        decode_chunk=k, **kw)
                assert e2.warmup() == {"prefill": 1, "insert": 1,
                                       "decode": n_exec}, (shape, k)
                r2 = e2.serve(list(reqs))
                for r in reqs:
                    assert r2.tokens(r.rid) == ref.tokens(r.rid), (
                        shape, k, r.rid)
                assert e2.compile_counts() == {"prefill": 1, "insert": 1,
                                               "decode": n_exec}, (shape, k)
                assert r2.observed_vectors == r2.useful_vectors, (shape, k)
                ls, st = reconcile(prog, r2.records, r2.observed_vectors)
                assert ls == st, (shape, k)
        print("CHUNKED_SHARDED_BITEQUAL_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        ["src", env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "CHUNKED_SHARDED_BITEQUAL_OK" in proc.stdout
