"""Continuous-batching engine invariants (runtime/engine.py, runtime/batcher.py).

The contracts pinned here (ISSUE 4 acceptance criteria):
  * synchronized arrivals through the engine are BIT-equal to the legacy
    static-batch path;
  * slot reuse after retirement never leaks stale KV/recurrent state;
  * per-request CM_* ledgers sum exactly to the `AimcProgram`'s static
    accounting;
  * shapes are jit-stable: serving a ragged Poisson trace never recompiles
    after warmup;
  * recurrent archs (xlstm, rglru) serve through per-slot state insertion;
  * ``max_new=1`` requests retire at prefill (no 0-step decode loop);
  * transient-vs-terminal failure classification for the decode loop.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.core.aimc import AimcConfig
from repro.core.program import MappingPlan, program_model
from repro.models.layers import Execution
from repro.runtime.batcher import (Batcher, Request, SlotAllocator,
                                   percentile, poisson_trace, reconcile,
                                   synchronized_trace)
from repro.runtime.engine import ServeEngine, static_generate
from repro.runtime.fault_tolerance import (StragglerMonitor, is_transient,
                                           resilient_step)

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
    return ServeEngine(model, cfg, EXE, kw.pop("params", params), **kw)


# ---------------------------------------------------------------------------
# bit-equality vs the legacy static-batch path
# ---------------------------------------------------------------------------

def test_sync_arrivals_bit_equal_static(tfm):
    spec, cfg, model, params = tfm
    eng = make_engine(tfm, n_slots=3)
    eng.warmup()
    reqs = synchronized_trace(3, prompt_len=8, max_new=6, seed=1,
                              vocab=cfg.vocab)
    report = eng.serve(reqs)
    prompts = jnp.asarray([r.prompt for r in reqs], jnp.int32)
    gen, _ = static_generate(model, cfg, EXE, params, prompts, 6, max_seq=24)
    for r in reqs:
        assert report.tokens(r.rid) == [int(t) for t in gen[r.rid]], \
            f"req {r.rid} diverged from the static path"


def test_gen1_requests_are_prefill_only(tfm):
    eng = make_engine(tfm)
    eng.warmup()
    reqs = synchronized_trace(4, prompt_len=6, max_new=1, seed=2, vocab=64)
    report = eng.serve(reqs)
    assert report.n_steps == 0                  # no 0-step decode loop
    assert report.n_prefills == 4
    for rec in report.records.values():
        assert len(rec.tokens) == 1
        assert rec.finish_reason == "length"
        assert rec.decode_vectors == 0
        assert rec.prefill_vectors == 6


# ---------------------------------------------------------------------------
# slot reuse / stale state
# ---------------------------------------------------------------------------

def test_slot_reuse_never_leaks_stale_kv(tfm):
    spec, cfg, model, params = tfm
    # 5 staggered requests through 2 slots: slots are retired and refilled
    # mid-stream. Every request's tokens must equal the same request served
    # through a FRESH engine (identical closure shapes), where no slot ever
    # held another request's state.
    reqs = [Request(rid=i, prompt=tuple(range(2 + i, 10)), max_new=2 + i,
                    arrival=0.0) for i in range(5)]
    eng = make_engine(tfm, n_slots=2)
    eng.warmup()
    report = eng.serve(reqs)
    for r in reqs:
        fresh = make_engine(tfm, n_slots=2)
        fresh.warmup()
        solo = fresh.serve([r])
        assert report.tokens(r.rid) == solo.tokens(r.rid), \
            f"req {r.rid}: slot reuse changed the output"


# ---------------------------------------------------------------------------
# shape stability
# ---------------------------------------------------------------------------

def test_no_recompile_after_warmup_on_ragged_trace(tfm):
    spec, cfg, model, params = tfm
    eng = make_engine(tfm, n_slots=3)
    counts = eng.warmup()
    assert counts == {"prefill": 1, "insert": 1, "decode": 1}
    reqs = poisson_trace(10, rate=400.0, seed=5, prompt_len=(2, 8),
                         max_new=(1, 7), vocab=cfg.vocab)
    report = eng.serve(reqs)
    assert len(report.records) == 10
    assert eng.compile_counts() == {"prefill": 1, "insert": 1, "decode": 1}, \
        "ragged trace recompiled an engine closure after warmup"


# ---------------------------------------------------------------------------
# CM_* ledger reconciliation (programmed AIMC path)
# ---------------------------------------------------------------------------

def test_ledgers_reconcile_with_program(tfm):
    spec, cfg, model, params = tfm
    aimc_cfg = AimcConfig(impl="ref")
    exe = Execution(mode="aimc", aimc=aimc_cfg, compute_dtype="float32",
                    programmed=True)
    program = program_model(params, MappingPlan(), aimc_cfg,
                            jax.random.PRNGKey(3))
    eng = ServeEngine(model, cfg, exe, program.install(params), n_slots=2,
                      prompt_pad=8, max_seq=20, family=spec.family,
                      module=spec.module, program=program)
    eng.warmup()
    reqs = poisson_trace(6, rate=300.0, seed=6, prompt_len=(3, 8),
                         max_new=(1, 5), vocab=cfg.vocab)
    report = eng.serve(reqs)
    # per-request ledger = per-vector counts x that request's vectors
    per_vec = program.mvm_counts()
    ledgers = eng.ledgers(report)
    for rid, rec in report.records.items():
        assert ledgers[rid] == per_vec.scaled(rec.vectors)
    # the device loop's own vector count (prompt lengths at prefill calls,
    # busy lanes at decode calls) must agree with the per-request books —
    # two independent countings, so a double-/under-count breaks this
    assert report.observed_vectors == report.useful_vectors
    # and the books close exactly against the program's static accounting
    ledger_sum, static = reconcile(program, report.records,
                                   report.observed_vectors)
    assert ledger_sum == static
    assert static == per_vec.scaled(report.useful_vectors)


# ---------------------------------------------------------------------------
# EOS retirement
# ---------------------------------------------------------------------------

def test_eos_retires_early(tfm):
    spec, cfg, model, params = tfm
    base = make_engine(tfm, n_slots=1)
    base.warmup()
    # force an early EOS: a token the free run FIRST emits at decode step 2
    # (ref[0] comes from the prefill), from the first prompt that has one
    for start in range(1, 64):
        req = Request(rid=0, prompt=tuple(range(start, start + 8)), max_new=8)
        ref = base.serve([req]).tokens(0)
        if ref[2] not in ref[:2]:
            break
    else:
        pytest.fail("no prompt emits a fresh token at decode step 2")
    assert len(ref) == 8
    eos = ref[2]
    eng = make_engine(tfm, n_slots=1, eos_id=eos)
    eng.warmup()
    report = eng.serve([req])
    rec = report.records[0]
    # the EOS is control, not payload: delivered tokens stop BEFORE it,
    # but its decode vector stays in the CM_* books (tokens holds the
    # prefill token plus decode_vectors - 1 delivered decode tokens)
    assert rec.tokens == ref[:2]
    assert rec.decode_vectors == len(rec.tokens)
    assert rec.finish_reason == "eos"


# ---------------------------------------------------------------------------
# recurrent archs serve through per-slot state insertion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["xlstm-350m", "recurrentgemma-9b"])
def test_recurrent_arch_serves_ragged_trace(arch):
    spec = get_arch(arch)
    cfg = spec.smoke_cfg
    model = spec.model_module()
    params = model.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(model, cfg, EXE, params, n_slots=2, prompt_pad=6,
                      max_seq=16, family=spec.family, module=spec.module,
                      cache_dtype=jnp.float32)
    eng.warmup()
    reqs = poisson_trace(5, rate=500.0, seed=7, prompt_len=(2, 6),
                         max_new=(1, 5), vocab=cfg.vocab)
    report = eng.serve(reqs)
    assert len(report.records) == 5
    assert eng.compile_counts() == {"prefill": 1, "insert": 1, "decode": 1}
    assert report.observed_vectors == report.useful_vectors
    for rec in report.records.values():
        assert 1 <= len(rec.tokens) <= rec.request.max_new
        assert rec.vectors == (len(rec.request.prompt)
                               + len(rec.tokens) - 1)


def test_recurrent_engine_matches_manual_decode_loop():
    spec = get_arch("xlstm-350m")
    cfg = spec.smoke_cfg
    model = spec.model_module()
    params = model.init(jax.random.PRNGKey(0), cfg)
    prompt = tuple(range(1, 7))
    eng = ServeEngine(model, cfg, EXE, params, n_slots=1, prompt_pad=6,
                      max_seq=16, family=spec.family, module=spec.module,
                      cache_dtype=jnp.float32)
    eng.warmup()
    got = eng.serve([Request(rid=0, prompt=prompt, max_new=5)]).tokens(0)
    # reference: feed the prompt token by token, then greedy-decode
    cache = model.init_cache(cfg, 1, 16, jnp.float32)
    toks = jnp.asarray(prompt, jnp.int32)[None]
    for t in range(len(prompt)):
        logits, cache = model.decode_step(params, cache, toks[:, t:t + 1],
                                          cfg, EXE)
    ref = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(4):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([[ref[-1]]], jnp.int32), cfg, EXE)
        ref.append(int(jnp.argmax(logits[0, -1])))
    assert got == ref


# ---------------------------------------------------------------------------
# transformer ragged decode == lockstep decode at equal lengths
# ---------------------------------------------------------------------------

def test_ragged_decode_matches_lockstep(tfm):
    spec, cfg, model, params = tfm
    toks = jax.random.randint(jax.random.PRNGKey(4), (3, 8), 1, cfg.vocab)
    _, cache = model.prefill(params, toks, cfg, EXE, max_seq=16,
                             cache_dtype=jnp.float32)
    nxt = jnp.ones((3, 1), jnp.int32)
    l_lock, c_lock = model.decode_step(params, cache, nxt, cfg, EXE)
    l_rag, c_rag = model.decode_step(params, cache, nxt, cfg, EXE,
                                     ragged=True)
    assert jnp.array_equal(l_lock, l_rag)
    assert all(jnp.array_equal(c_lock[k], c_rag[k]) for k in c_lock)


# ---------------------------------------------------------------------------
# batcher mechanics
# ---------------------------------------------------------------------------

def test_batcher_admission_and_slots():
    reqs = [Request(rid=0, prompt=(1,), arrival=0.5),
            Request(rid=1, prompt=(1,), arrival=0.0, max_new=9),
            Request(rid=2, prompt=(1,), arrival=0.0, max_new=2)]
    q = Batcher(reqs, policy="fifo")
    assert q.pop_ready(0.0).rid == 1             # arrival order, rid tiebreak
    assert q.pop_ready(0.0).rid == 2
    assert q.pop_ready(0.0) is None              # rid 0 hasn't arrived yet
    assert q.next_arrival() == 0.5
    assert q.pop_ready(0.5).rid == 0
    assert len(q) == 0

    q = Batcher(reqs, policy="sjf")
    assert q.pop_ready(0.0).rid == 2             # shortest max_new first
    # budget-first even under staggered arrivals: rid 0 (max_new=8) arrived
    # later than rid 1 (max_new=9) but is still admitted first
    assert q.pop_ready(0.5).rid == 0
    assert q.pop_ready(0.5).rid == 1

    slots = SlotAllocator(2)
    a, b = slots.alloc(10), slots.alloc(11)
    assert {a, b} == {0, 1} and slots.n_free == 0
    assert slots.release(a) == 10
    assert slots.alloc(12) == a                  # freed slot is reused

    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
    assert percentile([5.0], 99) == 5.0


def test_request_validation():
    with pytest.raises(ValueError):
        Request(rid=0, prompt=(), max_new=4)
    with pytest.raises(ValueError):
        Request(rid=0, prompt=(1,), max_new=0)


def test_prompt_longer_than_pad_rejected(tfm):
    eng = make_engine(tfm, n_slots=1, prompt_pad=4)
    eng.warmup()
    with pytest.raises(ValueError, match="exceeds"):
        eng.serve([Request(rid=0, prompt=tuple(range(1, 9)), max_new=2)])


# ---------------------------------------------------------------------------
# fault tolerance: transient vs terminal classification
# ---------------------------------------------------------------------------

def test_is_transient_classification():
    # infrastructure flakes retry
    assert is_transient(RuntimeError("UNAVAILABLE: connection reset"))
    assert is_transient(RuntimeError("DEADLINE_EXCEEDED: collective"))
    assert is_transient(OSError("stale file handle"))
    # deterministic failures are terminal — retrying replays the failure
    assert not is_transient(RuntimeError(
        "RESOURCE_EXHAUSTED: out of memory allocating 32.0GiB"))
    assert not is_transient(OSError("RESOURCE_EXHAUSTED: disk full"))
    assert not is_transient(RuntimeError("INVALID_ARGUMENT: shape mismatch"))
    # an unrecognized RuntimeError is a bug, not a flake
    assert not is_transient(RuntimeError("list index out of range"))
    assert not is_transient(ValueError("UNAVAILABLE"))   # wrong type


def test_resilient_step_raises_terminal_immediately():
    calls = []

    def oom(x):
        calls.append(x)
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    wrapped = resilient_step(oom, max_retries=3)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        wrapped(0)
    assert len(calls) == 1                       # no retry of an OOM


def test_resilient_step_does_not_retry_plain_bugs():
    calls = []

    def buggy(x):
        calls.append(x)
        raise RuntimeError("object has no attribute 'foo'")

    wrapped = resilient_step(buggy, max_retries=3)
    with pytest.raises(RuntimeError):
        wrapped(0)
    assert len(calls) == 1


def test_resilient_step_still_retries_flakes():
    calls = []

    def flaky(x):
        calls.append(x)
        if len(calls) < 3:
            raise RuntimeError("UNAVAILABLE: transient link error")
        return x + 1

    wrapped = resilient_step(flaky, max_retries=3)
    assert wrapped(1) == 2
    assert len(calls) == 3


def test_straggler_monitor_flags_slow_step_inside_warmup_window():
    # regression: the first sample alone used to seed the EWMA, so a slow
    # step at position 2..warmup could never be flagged
    mon = StragglerMonitor(threshold=2.0, alpha=0.1, warmup=3)
    assert not mon.record(1, 0.010)
    assert not mon.record(2, 0.010)
    assert not mon.record(3, 0.012)   # window full: median(0.010..0.012) seeds
    assert mon.record(4, 0.100)       # first post-seed sample CAN be flagged
    assert mon.flagged and mon.flagged[0][0] == 4


def test_straggler_monitor_slow_first_step_does_not_poison_baseline():
    # regression: a slow FIRST sample used to become the baseline, hiding
    # every later straggler behind an inflated EWMA
    mon = StragglerMonitor(threshold=2.0, alpha=0.1, warmup=3)
    mon.record(1, 0.500)              # slow outlier lands first
    mon.record(2, 0.010)
    mon.record(3, 0.010)
    assert abs(mon.ewma - 0.010) < 1e-12   # median seed ignores the outlier
    assert mon.record(4, 0.030)            # 3x the real baseline -> flagged
    # and the seeded baseline keeps tracking normal steps
    assert not mon.record(5, 0.011)
