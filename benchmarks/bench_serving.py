"""Serving-engine benchmark: continuous batching vs the legacy static batch.

The paper's deployment regime (weights stationary, tokens streaming) meets a
realistic request stream: staggered Poisson arrivals, ragged prompts,
per-request decode budgets. The legacy monolithic path must (a) WAIT for the
whole burst to arrive, (b) pad every prompt to one length, and (c) decode
the longest budget for everyone; the slot-based engine admits each request
on arrival, retires it at its own budget, and refills the slot immediately.

Measured per case (one transformer, one recurrent arch):
  * end-to-end throughput under the trace: useful tokens / makespan, where
    makespan runs from t=0 (first arrival is offset from it) to the last
    retirement — the continuous-batching win is the static path's dead
    arrival-wait + over-generation tail;
  * per-request latency percentiles (p50/p99) and TTFT;
  * CM_* ledger reconciliation on the programmed AIMC path;
  * engine compile counts (shape stability under the ragged trace);
  * bit-equality of engine vs static tokens for synchronized arrivals.

``--mesh data:D,model:M`` additionally benchmarks the SHARDED engine
(`runtime.engine.ShardedServeEngine`, DESIGN.md §11/§13) against the
single-device engine on the same traces: decode slots sharded over the data
axis, programmed crossbar bit lines over the model axis. The sharded sweep
runs the k-step chunked decode loop at every k in ``CHUNKS``: per-step host
rounds are what made the PR-5 sharded engine LOSE to one device (each
dispatch/sync round trip is paid per token), and the k-step `lax.scan`
chunk amortizes that round over k tokens.

The sharded gates are STEP-LEVEL, because on the forced host-platform mesh
the "devices" are threads sharing one physical CPU: total compute is
conserved, so an end-to-end cross-device speedup is not physically on the
table at smoke scale (per-call SPMD dispatch and thread contention are pure
overhead — the seed benchmark said as much). What the chunk is responsible
for — the per-token host round — IS measurable and gated: (a) the k=1 ->
k=max saturated step-time gain must exceed ``CHUNK_GAIN_MIN`` for the
arch where the round DOMINATES the step (the recurrent arch: its light
step makes the round ~half of k=1 cost and the sweep shows 1.6-2.2x;
the transformer's heavy step caps its ceiling at ~1.2-1.3x, inside
measurement noise, so its gain is recorded but not gated), (b) the
residual host-round share of the step at
k=max — (t_round/k) / T_step(k) from the fitted roofline — must fall
under ``ROUND_SHARE_MAX`` (the round no longer matters; at k=1 it is
25-50% of every step), and (c) each case fits
`core.schedule.OverlapRoofline`
(T_step(k) = t_step_s + t_round_s / k) to the measured per-step times and
gates the fit residual plus the PREDICTED 1->k overlap gain against the
MEASURED step-time delta — the speedup is explained, not just observed.
On real multi-device hardware the same discipline is what lets data:N win
end-to-end; here the end-to-end tok/s of every k is recorded for
transparency but not gated. Throughout, the chunked run must stay
BIT-EQUAL to the single-device engine and the per-core/per-request CM_*
ledgers must reconcile exactly (EXPERIMENTS.md §Sharded serving). The flag
forces ``--xla_force_host_platform_device_count`` as needed when run as a
module.

The paged case (DESIGN.md §15) benches the paged KV cache on a
shared-system-prompt trace: dense vs paged+chunked-prefill with the
content-hashed prefix cache off and on. Gated: bit-equality across all
three arms, prefix-on >= ``PREFIX_GAIN_MIN``x tok/s over prefix-off with
the shared span prefilled exactly once, chunked legs cutting the dense
path's prompt-pad waste (``prefill_pad_vectors`` before/after), and exact
CM_* + page-ledger reconciliation; the dense-vs-paged KV footprint and the
deduplicated shared-span bytes are recorded.

``--json BENCH_serving.json`` is the machine-readable artifact
(``benchmarks.run --json`` includes this module; ``make bench-json``).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import Check, table
from repro.configs import get_arch
from repro.core.aimc import AimcConfig
from repro.core.program import MappingPlan, program_model
from repro.models.layers import Execution
from repro.runtime.batcher import (poisson_trace, reconcile, reconcile_cores,
                                   synchronized_trace)
from repro.runtime.engine import (ServeEngine, ShardedServeEngine,
                                  static_generate)

N_REQ = 16
RATE = 100.0                 # req/s: arrivals overlap decode at smoke scale
PROMPT = (4, 12)
MAX_NEW = (2, 16)            # wide budget spread: static decodes max for all
PAD = 12
N_SLOTS = 4
CHUNKS = (1, 4, 8)           # decode_chunk sweep for the sharded engine
ROOFLINE_RTOL = 0.35         # fit residual / predicted-vs-measured gate
CHUNK_GAIN_MIN = 1.25        # k-sweep step gain where the round dominates
ROUND_SHARE_MAX = 0.20       # residual host-round share of the step at k=max

# paged-engine case (DESIGN.md §15): shared-system-prompt trace
P_PAD = 48                   # prompt pad — the system prompt dominates
P_SHARED = 40                # shared system-prompt span (5 full pages)
P_PAGE = 8                   # KV page size
P_CHUNK = 8                  # prefill-chunk leg width (both prefix arms)
P_REQ = 8                    # requests sharing the system prompt
PREFIX_GAIN_MIN = 1.3        # prefix-cache on/off tok/s gate


def _setup(arch: str, programmed: bool, n_contexts: int = 1):
    spec = get_arch(arch)
    cfg = spec.smoke_cfg
    model = spec.model_module()
    params = model.init(jax.random.PRNGKey(0), cfg)
    program = None
    if programmed:
        # fixed DAC input range (the deployment configuration): the dynamic
        # max-abs scale is computed over the whole flattened batch, so a
        # [1, P] engine prefill and a [B, P] static prefill would quantize
        # the same request differently — with a fixed scale the programmed
        # path is batch-size independent and engine == static bit-for-bit
        aimc_cfg = AimcConfig(input_scale=0.1)
        exe = Execution(mode="aimc", aimc=aimc_cfg, compute_dtype="float32",
                        programmed=True)
        program = program_model(params, MappingPlan(n_contexts=n_contexts),
                                aimc_cfg, jax.random.PRNGKey(2))
        params = program.install(params)
    else:
        exe = Execution(compute_dtype="float32")
    return spec, cfg, model, params, exe, program


def _serve_static_under_trace(model, cfg, exe, params, requests, max_seq):
    """The legacy path against a staggered trace: wait for the full burst,
    pad prompts to one length, decode the longest budget for everyone."""
    t_wait = max(r.arrival for r in requests)
    pad_id = 0
    prompts = jnp.asarray(
        [list(r.prompt) + [pad_id] * (PAD - len(r.prompt)) for r in requests],
        jnp.int32)
    gen = max(r.max_new for r in requests)
    # warm the static path's executables first — the engine's warmup is
    # outside its serving clock too, so neither side is billed for jit
    static_generate(model, cfg, exe, params, prompts, 2, max_seq=max_seq,
                    cache_dtype=jnp.float32)
    toks, (t_prefill, t_decode) = static_generate(
        model, cfg, exe, params, prompts, gen, max_seq=max_seq,
        cache_dtype=jnp.float32)
    makespan = t_wait + t_prefill + t_decode
    useful = sum(r.max_new for r in requests)
    over_gen = sum(gen - r.max_new for r in requests)
    lats = [makespan - r.arrival for r in requests]
    ttfts = [t_wait + t_prefill - r.arrival for r in requests]
    lats.sort()
    ttfts.sort()
    from repro.runtime.batcher import percentile
    return {
        "makespan_s": makespan,
        "useful_tokens": useful,
        "over_generated_tokens": over_gen,
        "tok_s": useful / makespan,
        "p50_latency_s": percentile(lats, 50),
        "p99_latency_s": percentile(lats, 99),
        "p50_ttft_s": percentile(ttfts, 50),
        "p99_ttft_s": percentile(ttfts, 99),
    }, toks


def _serve_continuous(engine, requests):
    report = engine.serve(requests)
    pct = report.latency_percentiles()
    return {
        "makespan_s": report.makespan_s,
        "useful_tokens": report.generated_tokens,
        "idle_lane_vectors": report.idle_vectors,
        "tok_s": report.generated_tokens / max(report.makespan_s, 1e-9),
        "n_decode_steps": report.n_steps,
        **pct,
    }, report


def _measure_step_time(engine, vocab: int, reps: int = 3) -> float:
    """Mean wall seconds per decode STEP (chunk wall / k) with every slot
    busy: a synchronized saturated trace keeps all lanes active so the
    measurement isolates host-round amortization, not slot raggedness.
    Best of ``reps`` serves shaves OS-scheduler noise off the roofline
    fit."""
    best = float("inf")
    for r in range(reps):
        sync = synchronized_trace(engine.n_slots, prompt_len=PAD,
                                  max_new=MAX_NEW[1], seed=5 + r,
                                  vocab=vocab)
        rep = engine.serve(sync)
        best = min(best, rep.wall_decode_s / max(rep.n_steps, 1))
    return best


def _bench_case(arch: str, programmed: bool, verbose: bool) -> dict:
    spec, cfg, model, params, exe, program = _setup(arch, programmed)
    max_seq = PAD + MAX_NEW[1] + 2
    engine = ServeEngine(model, cfg, exe, params, n_slots=N_SLOTS,
                         prompt_pad=PAD, max_seq=max_seq,
                         cache_dtype=jnp.float32, family=spec.family,
                         module=spec.module, program=program)
    t0 = time.time()
    engine.warmup()
    t_warm = time.time() - t0

    trace = poisson_trace(N_REQ, RATE, seed=11, prompt_len=PROMPT,
                          max_new=MAX_NEW, vocab=cfg.vocab)
    cont, report = _serve_continuous(engine, trace)
    stat, _ = _serve_static_under_trace(model, cfg, exe, params, trace,
                                        max_seq)

    # synchronized arrivals: engine tokens must be bit-equal to static
    sync = synchronized_trace(N_SLOTS, prompt_len=PAD, max_new=6, seed=3,
                              vocab=cfg.vocab)
    sync_rep = engine.serve(sync)
    prompts = jnp.asarray([r.prompt for r in sync], jnp.int32)
    sync_toks, _ = static_generate(model, cfg, exe, params, prompts, 6,
                                   max_seq=max_seq, cache_dtype=jnp.float32)
    bit_equal = all(sync_rep.tokens(r.rid) == [int(t) for t in sync_toks[i]]
                    for i, r in enumerate(sync))

    # the ledger check crosses two independent countings: per-request
    # records vs the device loop's observed prefill/busy-lane vectors
    ledger_exact = report.observed_vectors == report.useful_vectors
    if program is not None:
        led_sum, static_sum = reconcile(program, report.records,
                                        report.observed_vectors)
        ledger_exact = ledger_exact and led_sum == static_sum

    case = {
        "arch": spec.arch_id,
        "exec": "aimc-programmed" if programmed else "digital",
        "trace": f"poisson:{RATE:.0f} n={N_REQ} prompt={PROMPT} "
                 f"max_new={MAX_NEW}",
        "n_slots": N_SLOTS,
        "warmup_s": t_warm,
        "continuous": cont,
        "static": stat,
        "tok_s_ratio": cont["tok_s"] / max(stat["tok_s"], 1e-9),
        "compile_counts": engine.compile_counts(),
        "stable_shapes": engine.compile_counts()
        == {"prefill": 1, "insert": 1, "decode": 1},
        "sync_bit_equal": bit_equal,
        "ledger_exact": ledger_exact,
    }
    if verbose:
        rows = [[mode, f"{d['tok_s']:.1f}", f"{d['makespan_s'] * 1e3:.0f}",
                 f"{d['p50_latency_s'] * 1e3:.0f}",
                 f"{d['p99_latency_s'] * 1e3:.0f}",
                 f"{d['p50_ttft_s'] * 1e3:.0f}"]
                for mode, d in (("static", stat), ("continuous", cont))]
        print(table(
            f"{spec.arch_id} [{case['exec']}] — {case['trace']}",
            ["path", "tok/s", "makespan ms", "p50 lat ms", "p99 lat ms",
             "p50 ttft ms"], rows))
        print(f"  continuous/static tok/s ratio: {case['tok_s_ratio']:.2f}  "
              f"(static over-generated {stat['over_generated_tokens']} "
              f"tokens, waited {max(r.arrival for r in trace) * 1e3:.0f}ms "
              f"for the burst)")
        print(f"  shape-stable: {case['stable_shapes']}  "
              f"sync bit-equal: {bit_equal}  ledger exact: {ledger_exact}")
    return case


def _bench_sharded_case(arch: str, programmed: bool, mesh, mesh_arg: str,
                        verbose: bool, chunks=CHUNKS) -> dict:
    """Sharded chunked-decode sweep vs the single-device engine on
    identical traces (DESIGN.md §11/§13): same params/program/trace, the
    variables are the mesh placement and the decode chunk size k. Fits
    `OverlapRoofline` to the measured per-step times across k and records
    both the predicted and the realized overlap gain."""
    from repro.core.schedule import CoreSchedule, OverlapRoofline
    n_ctx = max(2, mesh.shape.get("model", 1)) if programmed else 1
    spec, cfg, model, params, exe, program = _setup(arch, programmed, n_ctx)
    schedule = (CoreSchedule.from_program(program)
                if program is not None else None)
    max_seq = PAD + MAX_NEW[1] + 2
    kw = dict(n_slots=N_SLOTS, prompt_pad=PAD, max_seq=max_seq,
              cache_dtype=jnp.float32, family=spec.family,
              module=spec.module, program=program, schedule=schedule)
    single = ServeEngine(model, cfg, exe, params, **kw)
    single.warmup()

    trace = poisson_trace(N_REQ, RATE, seed=11, prompt_len=PROMPT,
                          max_new=MAX_NEW, vocab=cfg.vocab)
    cont_single, _ = _serve_continuous(single, trace)
    cont_single["step_s"] = _measure_step_time(single, cfg.vocab)
    sync = synchronized_trace(N_SLOTS, prompt_len=PAD, max_new=6, seed=3,
                              vocab=cfg.vocab)
    sync_single = single.serve(sync)

    by_chunk = {}
    step_times = {}
    bit_equal = ledger_exact = stable = True
    t_warm = 0.0
    best_k = chunks[0]
    for k in chunks:
        t0 = time.time()
        sharded = ShardedServeEngine(model, cfg, exe, params, mesh=mesh,
                                     decode_chunk=k, **kw)
        sharded.warmup()
        t_warm += time.time() - t0
        cont_sharded, rep_sharded = _serve_continuous(sharded, trace)
        step_times[k] = _measure_step_time(sharded, cfg.vocab)
        cont_sharded["step_s"] = step_times[k]

        # the equality bar AT EVERY k: the same trace decodes to the same
        # tokens on the mesh, whatever the chunk size (every request,
        # every token)
        sync_sharded = sharded.serve(sync)
        bit_equal = bit_equal and all(
            sync_single.tokens(r.rid) == sync_sharded.tokens(r.rid)
            for r in sync)
        ok = rep_sharded.observed_vectors == rep_sharded.useful_vectors
        if program is not None:
            led_sum, static_sum = reconcile(program, rep_sharded.records,
                                            rep_sharded.observed_vectors)
            core_sum, sched_total = reconcile_cores(
                schedule, rep_sharded.records, rep_sharded.observed_vectors)
            ok = (ok and led_sum == static_sum and core_sum == sched_total
                  and sched_total == program.mvm_counts().scaled(
                      rep_sharded.observed_vectors))
        ledger_exact = ledger_exact and ok
        # decode holds one executable per compiled ladder length (powers of
        # two up to k), all built at warmup; serving must not add any
        stable = stable and (sharded.compile_counts()
                             == {"prefill": 1, "insert": 1,
                                 "decode": len(sharded._ladder)})
        by_chunk[str(k)] = cont_sharded
        if cont_sharded["tok_s"] > by_chunk[str(best_k)]["tok_s"]:
            best_k = k

    # calibrated overlap roofline: T_step(k) = t_step_s + t_round_s / k.
    # predicted 1->k_max gain must EXPLAIN the measured step-time delta.
    roofline = OverlapRoofline.fit(step_times)
    k_lo, k_hi = min(chunks), max(chunks)
    measured_gain = step_times[k_lo] / max(step_times[k_hi], 1e-12)
    predicted_gain = roofline.speedup(k_lo, k_hi)
    residual = max(roofline.residuals(step_times).values())

    best = by_chunk[str(best_k)]
    # best-k sharded per-step cost relative to the single-device engine's
    # (recorded for transparency: the residual over 1.0 at large k is SPMD
    # compute overhead — the thread-devices split one CPU — not the host
    # round, which the gated round-share isolates)
    step_ratio = (min(step_times.values())
                  / max(cont_single["step_s"], 1e-12))
    # the gated step-level recovery: what fraction of a step is still the
    # host round at k=k_hi, per the fitted roofline (25-50% at k=1)
    round_share = ((roofline.t_round_s / k_hi)
                   / max(roofline.predict_step_s(k_hi), 1e-12))
    case = {
        "arch": spec.arch_id,
        "exec": "aimc-programmed" if programmed else "digital",
        "mesh": mesh_arg,
        "trace": f"poisson:{RATE:.0f} n={N_REQ} prompt={PROMPT} "
                 f"max_new={MAX_NEW}",
        "n_slots": N_SLOTS,
        "chunks": list(chunks),
        "warmup_s": t_warm,
        "single": cont_single,
        "sharded_by_chunk": by_chunk,
        "best_chunk": best_k,
        "sharded": best,
        "tok_s_ratio": best["tok_s"] / max(cont_single["tok_s"], 1e-9),
        "tok_s_ratio_k1": (by_chunk[str(k_lo)]["tok_s"]
                           / max(cont_single["tok_s"], 1e-9)),
        "step_ratio": step_ratio,
        "chunk_step_gain": step_times[k_lo] / max(min(step_times.values()),
                                                  1e-12),
        "round_share_k_hi": round_share,
        "round_share_k1": (roofline.t_round_s
                           / max(roofline.predict_step_s(k_lo), 1e-12)),
        "roofline": {
            "t_step_s": roofline.t_step_s,
            "t_round_s": roofline.t_round_s,
            "fit_residual_max": residual,
            "predicted_gain": predicted_gain,
            "measured_gain": measured_gain,
            "k_lo": k_lo, "k_hi": k_hi,
        },
        "stable_shapes": stable,
        "sync_bit_equal": bit_equal,
        "ledger_exact": ledger_exact,
    }
    if verbose:
        rows = [["single k=1", f"{cont_single['tok_s']:.1f}",
                 f"{cont_single['step_s'] * 1e3:.2f}",
                 f"{cont_single['makespan_s'] * 1e3:.0f}",
                 f"{cont_single['p50_latency_s'] * 1e3:.0f}",
                 f"{cont_single['p99_latency_s'] * 1e3:.0f}"]]
        rows += [[f"sharded k={k}", f"{d['tok_s']:.1f}",
                  f"{d['step_s'] * 1e3:.2f}",
                  f"{d['makespan_s'] * 1e3:.0f}",
                  f"{d['p50_latency_s'] * 1e3:.0f}",
                  f"{d['p99_latency_s'] * 1e3:.0f}"]
                 for k, d in by_chunk.items()]
        print(table(
            f"{spec.arch_id} [{case['exec']}] engine on mesh {mesh_arg}",
            ["engine", "tok/s", "step ms", "makespan ms", "p50 lat ms",
             "p99 lat ms"], rows))
        print(f"  best chunk k={best_k}: sharded/single tok/s ratio "
              f"{case['tok_s_ratio']:.2f} (was {case['tok_s_ratio_k1']:.2f}"
              f" at k=1); per-step cost {case['step_ratio']:.2f}x single, "
              f"chunk step gain {case['chunk_step_gain']:.2f}x over k=1, "
              f"host-round share {case['round_share_k1']:.0%} -> "
              f"{case['round_share_k_hi']:.0%}")
        print(f"  roofline: t_step={roofline.t_step_s * 1e3:.2f}ms "
              f"t_round={roofline.t_round_s * 1e3:.2f}ms  "
              f"predicted {k_lo}->{k_hi} gain {predicted_gain:.2f}x vs "
              f"measured {measured_gain:.2f}x  (max residual "
              f"{residual:.2%})")
        print(f"  shape-stable: {stable}  sync bit-equal: {bit_equal}  "
              f"ledger exact: {ledger_exact}")
    return case


def _shared_prompt_trace(n: int, vocab: int, seed: int = 7):
    """``n`` synchronized requests sharing one ``P_SHARED``-token system
    prompt, each with a unique 4..(P_PAD - P_SHARED)-token suffix — the
    deployment shape the content-hashed prefix cache exists for."""
    import random

    from repro.runtime.batcher import Request
    rng = random.Random(seed)
    shared = tuple(rng.randint(1, vocab - 1) for _ in range(P_SHARED))
    out = []
    for i in range(n):
        sfx = tuple(rng.randint(1, vocab - 1)
                    for _ in range(rng.randint(4, P_PAD - P_SHARED)))
        out.append(Request(rid=i, prompt=shared + sfx, max_new=3,
                           arrival=0.0))
    return out


def _cache_bytes(engine) -> int:
    """Total bytes of the engine's session KV storage (dense slot cache or
    paged pools + page table)."""
    return sum(x.nbytes for x in
               jax.tree_util.tree_leaves(engine._empty_cache()))


def _bench_paged_case(verbose: bool) -> dict:
    """Paged KV cache + content-hashed prefix cache + chunked prefill
    (DESIGN.md §15) on a shared-system-prompt trace.

    Three engines over the SAME trace — dense (the before: every prefill
    pays the full ``P_PAD`` pad width and every request re-prefills the
    shared span), paged+chunked with the prefix cache OFF, and the same
    with it ON. Single decode slot so admission order is deterministic and
    the exactly-once contract is checkable under chunking: request 0
    produces the shared pages, every later admission hits them and prefills
    only its unique suffix. Gates: bit-equality across all three engines,
    prefix-on >= ``PREFIX_GAIN_MIN``x tok/s over prefix-off (same chunking,
    the ONLY toggle is the prefix cache), shared span prefilled exactly
    once, chunked legs cut the dense path's prompt-pad waste, and CM_* +
    page ledgers reconcile exactly."""
    spec, cfg, model, params, exe, program = _setup("granite-8b", True)
    max_seq = P_PAD + 8
    n_pages = 16            # one max-length request + the held prefix + slack
    kw = dict(n_slots=1, prompt_pad=P_PAD, max_seq=max_seq,
              cache_dtype=jnp.float32, family=spec.family,
              module=spec.module, program=program)
    trace = _shared_prompt_trace(P_REQ, cfg.vocab)
    plens = [len(r.prompt) for r in trace]

    arms = {}
    reports = {}
    counts = {}
    bytes_of = {}
    engines = {}
    for name, extra in (
            ("dense", {}),
            ("paged_off", dict(page_size=P_PAGE, n_pages=n_pages,
                               prefill_chunk=P_CHUNK)),
            ("paged_on", dict(page_size=P_PAGE, n_pages=n_pages,
                              prefill_chunk=P_CHUNK, prefix_cache=True))):
        eng = ServeEngine(model, cfg, exe, params, **kw, **extra)
        counts0 = eng.warmup()
        bytes_of[name] = _cache_bytes(eng)
        stats, rep = _serve_continuous(eng, list(trace))
        ok = rep.observed_vectors == rep.useful_vectors
        led_sum, static_sum = reconcile(program, rep.records,
                                        rep.observed_vectors)
        ok = ok and led_sum == static_sum and rep.page_ledger_exact
        stats["ledger_exact"] = ok
        stats["stable_shapes"] = eng.compile_counts() == counts0
        stats["prefill_pad_vectors"] = rep.prefill_pad_vectors
        arms[name] = stats
        reports[name] = rep
        counts[name] = eng.compile_counts()
        engines[name] = eng

    bit_equal = all(
        reports["dense"].tokens(r.rid) == reports[name].tokens(r.rid)
        for r in trace for name in ("paged_off", "paged_on"))

    on = reports["paged_on"]
    # exactly-once: request 0 pays its full prompt, every other request
    # pays ONLY its continuation past the page-aligned shared span
    span = (P_SHARED // P_PAGE) * P_PAGE
    paid = [on.records[r.rid].prefill_vectors for r in trace]
    exactly_once = (
        on.prefix_hits == P_REQ - 1
        and on.prefix_hit_vectors == span * (P_REQ - 1)
        and paid == [plens[0]] + [p - span for p in plens[1:]])

    gain = arms["paged_on"]["tok_s"] / max(arms["paged_off"]["tok_s"], 1e-9)

    # pad-waste before/after on a RAGGED short-prompt trace: the dense
    # path pads every prompt to P_PAD rows, so prompts far below the pad
    # burn (P_PAD - plen) lanes each; chunked legs only round up to the
    # leg width. (The shared-prompt trace above sits near the pad on
    # purpose, so it can't show this.) Engines are reusable across serves.
    ragged = poisson_trace(P_REQ, RATE, seed=13, prompt_len=(4, 12),
                           max_new=(2, 4), vocab=cfg.vocab)
    pad_waste = {}
    for name in ("dense", "paged_on"):
        eng = engines[name]
        rep = eng.serve(list(ragged))
        pad_waste[name] = rep.prefill_pad_vectors
    pad_cut = pad_waste["paged_on"] < pad_waste["dense"]

    # footprint: dense stores the shared span once PER SLOT CONTEXT; the
    # paged pool stores it once, refcounted. Bytes per token row derived
    # from the dense cache (covers K+V across all layers).
    row_bytes = bytes_of["dense"] // max_seq        # n_slots=1
    case = {
        "arch": spec.arch_id,
        "exec": "aimc-programmed",
        "trace": f"sync n={P_REQ} shared_prefix={P_SHARED} "
                 f"prompt<=P_PAD={P_PAD} max_new=3",
        "page_size": P_PAGE, "n_pages": n_pages, "prefill_chunk": P_CHUNK,
        "arms": arms,
        "prefix_tok_s_gain": gain,
        "prefix_hits": on.prefix_hits,
        "prefix_hit_vectors": on.prefix_hit_vectors,
        "prefill_vectors_paid": paid,
        "exactly_once": exactly_once,
        "pad_trace": f"poisson:{RATE:.0f} n={P_REQ} prompt=(4, 12) "
                     f"max_new=(2, 4)",
        "pad_waste_before": pad_waste["dense"],
        "pad_waste_after": pad_waste["paged_on"],
        "pad_waste_cut": pad_cut,
        "footprint": {
            "dense_cache_bytes": bytes_of["dense"],
            "paged_cache_bytes": bytes_of["paged_on"],
            "row_bytes": row_bytes,
            # KV bytes the prefix cache avoids duplicating across the trace
            "shared_span_bytes_saved": (P_REQ - 1) * span * row_bytes,
        },
        "compile_counts": counts["paged_on"],
        "sync_bit_equal": bit_equal,
        "stable_shapes": all(a["stable_shapes"] for a in arms.values()),
        "ledger_exact": all(a["ledger_exact"] for a in arms.values()),
    }
    if verbose:
        rows = [[name, f"{a['tok_s']:.1f}",
                 f"{a['makespan_s'] * 1e3:.0f}",
                 f"{a['prefill_pad_vectors']}",
                 f"{a['p50_ttft_s'] * 1e3:.0f}"]
                for name, a in arms.items()]
        print(table(
            f"{spec.arch_id} [aimc-programmed] paged engine — "
            f"{case['trace']}",
            ["arm", "tok/s", "makespan ms", "pad waste", "p50 ttft ms"],
            rows))
        fp = case["footprint"]
        print(f"  prefix cache on/off tok/s gain: {gain:.2f}x "
              f"(gate >= {PREFIX_GAIN_MIN}x); hits {on.prefix_hits}/"
              f"{P_REQ - 1}, {on.prefix_hit_vectors} prompt vectors never "
              f"re-prefilled; exactly-once: {exactly_once}")
        print(f"  ragged-trace pad waste {case['pad_waste_before']} -> "
              f"{case['pad_waste_after']} vectors (full-pad prefill vs "
              f"chunk={P_CHUNK} legs); shared-span KV deduplicated: "
              f"{fp['shared_span_bytes_saved'] / 1e6:.2f} MB across "
              f"{P_REQ} requests")
        print(f"  bit-equal: {bit_equal}  shape-stable: "
              f"{case['stable_shapes']}  ledger exact: "
              f"{case['ledger_exact']}")
    return case


def _bench_drift_case(arch: str, verbose: bool) -> dict:
    """Drift-aware serving (DESIGN.md §14): accuracy vs program age, the
    hot-recalibration cost, and a chaos-grade mid-trace kill.

    Three legs, three gates:
      * probe error GROWS with program age under the power-law drift model
        and crosses the health threshold (there is something to repair);
      * after hot recalibration the probe error returns to the FRESH
        tolerance — reprogramming under the original keys is bit-exact,
        so the recovery is exact, not approximate;
      * a deterministic mid-trace core kill through the engine loses zero
        requests, stays bit-equal to the unfaulted run, and closes the
        CM_* books exactly INCLUDING the extra recal CM_INITIALIZE.
    """
    from repro.core import noise as noise_lib
    from repro.core.schedule import CoreSchedule
    from repro.runtime.chaos import parse_chaos
    from repro.runtime.health import build_health, reconcile_recal

    spec = get_arch(arch)
    cfg = spec.smoke_cfg
    model = spec.model_module()
    params = model.init(jax.random.PRNGKey(0), cfg)
    aimc_cfg = AimcConfig(input_scale=0.1)
    exe = Execution(mode="aimc", aimc=aimc_cfg, compute_dtype="float32",
                    programmed=True)
    plan = MappingPlan(n_contexts=2)
    key = jax.random.PRNGKey(2)
    program = program_model(params, plan, aimc_cfg, key)
    schedule = CoreSchedule.from_program(program)

    # -- accuracy vs program age (probe error against the fresh oracle) -----
    drift = noise_lib.drift_only(nu=0.05, t0=0.01)
    health = build_health(program, params, plan, key, noise=drift)
    fresh = dict(zip(program.names, program.states))
    err_fresh = max(health.probe(fresh, 0.0).errors.values())
    age_curve = {}
    for age in (0.01, 0.1, 1.0, 10.0, 100.0):
        entries = program.aged_entries(age, drift) or fresh
        sample = health.probe(entries, age)
        age_curve[str(age)] = max(sample.errors.values())
    t_old = 100.0
    aged = program.aged_entries(t_old, drift)
    failing = health.failing_cores(health.probe(aged, t_old))
    t0 = time.time()
    entries, names, cm = health.recalibrate(failing, t_old)
    recal_wall_s = time.time() - t0
    err_recal = max(health.probe(
        {**aged, **entries}, t_old + 1e-3).errors.values())

    # -- chaos leg: mid-trace kill through the engine ------------------------
    max_seq = PAD + MAX_NEW[1] + 2
    kw = dict(n_slots=N_SLOTS, prompt_pad=PAD, max_seq=max_seq,
              cache_dtype=jnp.float32, family=spec.family,
              module=spec.module, program=program, schedule=schedule,
              decode_chunk=4)
    trace = poisson_trace(N_REQ, RATE, seed=11, prompt_len=PROMPT,
                          max_new=MAX_NEW, vocab=cfg.vocab)
    ref_eng = ServeEngine(model, cfg, exe, program.install(params), **kw)
    ref_eng.warmup()
    ref = ref_eng.serve(list(trace))

    chaos = parse_chaos("kill:1@2")
    chaos_health = build_health(program, params, plan, key)
    eng = ServeEngine(model, cfg, exe, program.install(params),
                      health=chaos_health, chaos=chaos, **kw)
    eng.warmup()
    rep = eng.serve(list(trace))

    lost = len(trace) - len(rep.records)
    bit_equal = all(rep.tokens(r.rid) == ref.tokens(r.rid) for r in trace)
    led_sum, static_sum = reconcile(eng.program, rep.records,
                                    rep.observed_vectors)
    books_exact = (lost == 0 and chaos.exhausted and rep.n_recals >= 1
                   and led_sum == static_sum
                   and reconcile_recal(eng.program, rep)
                   and rep.recal_initialize > 0)

    session_init = program.initialize_counts().initialize
    case = {
        "arch": spec.arch_id,
        "drift": {"nu": drift.drift_nu, "t0_s": drift.drift_t0},
        "health_threshold": health.policy.threshold,
        "probe_err_fresh": err_fresh,
        "probe_err_by_age_s": age_curve,
        "probe_err_after_recal": err_recal,
        "recal": {
            "cores": list(failing),
            "n_matrices": len(names),
            "cm_initialize": cm.initialize,
            "session_cm_initialize": session_init,
            "cost_vs_session": cm.initialize / max(session_init, 1),
            "wall_s": recal_wall_s,
        },
        "chaos": {
            "spec": "kill:1@2",
            "lost_requests": lost,
            "n_recals": rep.n_recals,
            "recal_cm_initialize": rep.recal_initialize,
            "probes": rep.probes,
            "wall_health_s": rep.wall_health_s,
            "bit_equal": bit_equal,
            "books_exact": books_exact,
            "straggler_exempted": len(eng.monitor.exempted),
        },
        "drift_detected": age_curve[str(t_old)] > health.policy.threshold,
        "recal_recovers": err_recal <= err_fresh + 1e-6,
    }
    if verbose:
        rows = [[age, f"{err:.4f}"] for age, err in age_curve.items()]
        rows.append(["after recal", f"{err_recal:.4f}"])
        print(table(
            f"{spec.arch_id} [aimc-programmed] drift nu={drift.drift_nu:g} "
            f"t0={drift.drift_t0:g}s — max per-core probe error",
            ["program age (s)", "rel err"], rows))
        print(f"  recal: {len(names)} matrices on cores {list(failing)}, "
              f"CM_INITIALIZE={cm.initialize} "
              f"({case['recal']['cost_vs_session']:.0%} of the session "
              f"program bill), {recal_wall_s * 1e3:.0f}ms wall")
        print(f"  chaos kill:1@2: lost={lost} bit-equal={bit_equal} "
              f"books-exact={books_exact} recal CM_INITIALIZE="
              f"{rep.recal_initialize} exempted-chunks="
              f"{case['chaos']['straggler_exempted']}")
    return case


def run(verbose: bool = True, mesh_arg: str | None = None) -> dict:
    cases = [
        _bench_case("granite-8b", programmed=True, verbose=verbose),
        _bench_case("xlstm-350m", programmed=False, verbose=verbose),
    ]
    out = {"cases": cases,
           "paged_case": _bench_paged_case(verbose=verbose),
           "drift_case": _bench_drift_case("granite-8b", verbose=verbose)}
    if mesh_arg:
        from repro.launch.mesh import make_mesh
        from repro.launch.serve import parse_named_mesh
        shape, axes = parse_named_mesh(mesh_arg)
        mesh = make_mesh(shape, axes)
        out["sharded_cases"] = [
            _bench_sharded_case("granite-8b", True, mesh, mesh_arg, verbose),
            _bench_sharded_case("xlstm-350m", False, mesh, mesh_arg,
                                verbose),
        ]
    return out


def checks(results=None) -> list[Check]:
    results = results or run(verbose=False)
    cases = results["cases"]
    min_ratio = min(c["tok_s_ratio"] for c in cases)
    out = [
        Check("continuous batching beats static tok/s on every "
              "staggered trace",
              1.0 if min_ratio > 1.0 else 0.0, 1.0, rtol=0.01),
        Check("engine shapes jit-stable over ragged traces (no recompile)",
              1.0 if all(c["stable_shapes"] for c in cases) else 0.0,
              1.0, rtol=0.01),
        Check("synchronized arrivals bit-equal to the static path",
              1.0 if all(c["sync_bit_equal"] for c in cases) else 0.0,
              1.0, rtol=0.01),
        Check("per-request CM_* ledgers reconcile with AimcProgram",
              1.0 if all(c["ledger_exact"] for c in cases) else 0.0,
              1.0, rtol=0.01),
    ]
    paged = results.get("paged_case")
    if paged:
        out += [
            Check("paged engine bit-equal to dense on the shared-prompt "
                  "trace (prefix on and off)",
                  1.0 if paged["sync_bit_equal"] else 0.0, 1.0, rtol=0.01),
            Check("prefix cache beats prefix-off tok/s on the shared-"
                  f"system-prompt trace (>= {PREFIX_GAIN_MIN}x)",
                  1.0 if paged["prefix_tok_s_gain"] >= PREFIX_GAIN_MIN
                  else 0.0, 1.0, rtol=0.01),
            Check("shared system-prompt span prefilled exactly once "
                  "(every later request pays only its suffix)",
                  1.0 if paged["exactly_once"] else 0.0, 1.0, rtol=0.01),
            Check("chunked prefill cuts prompt-pad waste vs the dense "
                  "full-pad prefill",
                  1.0 if paged["pad_waste_cut"] else 0.0, 1.0, rtol=0.01),
            Check("paged arms: CM_* + page ledgers reconcile, shapes "
                  "jit-stable",
                  1.0 if paged["ledger_exact"] and paged["stable_shapes"]
                  else 0.0, 1.0, rtol=0.01),
        ]
    drift_case = results.get("drift_case")
    if drift_case:
        ch = drift_case["chaos"]
        out += [
            Check("conductance drift degrades probe accuracy past the "
                  "health threshold with program age",
                  1.0 if drift_case["drift_detected"] else 0.0, 1.0,
                  rtol=0.01),
            Check("hot recalibration recovers probe error to the fresh "
                  "tolerance (bit-exact reprogram)",
                  1.0 if drift_case["recal_recovers"] else 0.0, 1.0,
                  rtol=0.01),
            Check("mid-trace core kill: zero lost requests, books exact "
                  "incl. recal CM_INITIALIZE",
                  1.0 if ch["books_exact"] else 0.0, 1.0, rtol=0.01),
            Check("chaos run tokens bit-equal to the unfaulted run",
                  1.0 if ch["bit_equal"] else 0.0, 1.0, rtol=0.01),
        ]
    sharded = results.get("sharded_cases")
    if sharded:
        max_round_share = max(c["round_share_k_hi"] for c in sharded)
        # gate the raw sweep gain on the arch where the round dominates
        # the k=1 step (max across cases): a heavy-step arch's gain
        # ceiling is ~1.2x and sits inside noise — its recovery is gated
        # by the normalized round share instead
        best_chunk_gain = max(c["chunk_step_gain"] for c in sharded)
        max_resid = max(c["roofline"]["fit_residual_max"] for c in sharded)
        gain_explained = all(
            abs(c["roofline"]["predicted_gain"]
                - c["roofline"]["measured_gain"])
            <= ROOFLINE_RTOL * c["roofline"]["measured_gain"]
            for c in sharded)
        out += [
            Check("sharded engine bit-equal to single-device at every "
                  "chunk size",
                  1.0 if all(c["sync_bit_equal"] for c in sharded) else 0.0,
                  1.0, rtol=0.01),
            Check("sharded engine shapes jit-stable (no recompile)",
                  1.0 if all(c["stable_shapes"] for c in sharded) else 0.0,
                  1.0, rtol=0.01),
            Check("shard-aggregated per-core ledgers reconcile exactly",
                  1.0 if all(c["ledger_exact"] for c in sharded) else 0.0,
                  1.0, rtol=0.01),
            Check("chunked decode amortizes the per-token host round "
                  f"(k sweep step gain >= {CHUNK_GAIN_MIN}x where the "
                  "round dominates)",
                  1.0 if best_chunk_gain >= CHUNK_GAIN_MIN else 0.0, 1.0,
                  rtol=0.01),
            Check("host round reduced to a minor share of the k=max step "
                  f"(<= {ROUND_SHARE_MAX:.0%} per roofline)",
                  1.0 if max_round_share <= ROUND_SHARE_MAX else 0.0, 1.0,
                  rtol=0.01),
            Check("overlap roofline fit residual within gate",
                  1.0 if max_resid <= ROOFLINE_RTOL else 0.0, 1.0,
                  rtol=0.01),
            Check("roofline-predicted overlap gain matches measured",
                  1.0 if gain_explained else 0.0, 1.0, rtol=0.01),
        ]
    return out


if __name__ == "__main__":
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH",
                    help="write results + checks as JSON")
    ap.add_argument("--mesh", metavar="SPEC", default=None,
                    help="also bench the sharded engine on this mesh "
                         "(data:D,model:M); forces host-platform device "
                         "count as needed")
    args = ap.parse_args()
    if args.mesh:
        # must precede first backend use: XLA fixes the device count at init
        from repro.launch.serve import force_host_device_count
        force_host_device_count(args.mesh)
    res = run(mesh_arg=args.mesh)
    cs = checks(res)
    for c in cs:
        print(c.row())
    if args.json:
        payload = {"results": res,
                   "checks": [{"name": c.name, "measured": c.measured,
                               "target": c.target, "ok": c.ok} for c in cs]}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")
    sys.exit(0 if all(c.ok for c in cs) else 1)
