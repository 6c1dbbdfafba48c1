#!/usr/bin/env python3
"""Smoke test: the served AIMC path on a TPU, through its normal entry points.

    python chip_smoke.py                # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips   # four chips: the sharded phase only

One process. It exits nonzero, and prints no result line, unless JAX's first
device is a TPU. Earlier lines print the device, the depth cut, compile
seconds and every check; the last line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Phases on one chip, granite_8b at its published widths (d_model 4096,
GQA 32:8, d_ff 14336, vocab 49152) with random weights from ``--seed``:

  (a) the compiled AIMC kernels (`aimc_matmul_v2`, `aimc_matmul_stacked`)
      against the jnp oracle on the same chip, read noise off and on;
  (b) AIMC serving: `program_model` -> `install` -> `ServeEngine.warmup`
      -> `serve`, as ``launch/serve.py`` does it. Every request is served,
      CM_* ledgers reconcile with `program.mvm_counts()`, nothing
      recompiles after warmup, tokens equal `static_generate` under a fixed
      DAC input scale (a one-slot engine against batches of 1, and the
      4-slot engine against its closures with every prompt prefilled alone,
      as the engine prefills, and the 4 rows decoded together), and the
      decode closure holds the Mosaic kernel;
  (c) digital serving of the same model in bf16: every request served.

``--four-chips`` runs only `ShardedServeEngine` on ``data:2,model:2``
against the single-device engine in the same process: the kernel dispatch
on the mesh bit-equal to one device (noise on), every AIMC request served
with per-request, per-core and per-device ledgers reconciled, and the
digital float32 model's tokens bit-equal. AIMC tokens across the two are
counted and logged (see `four_chips`).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
DEPTH = 4               # of granite_8b's 36 layers: ~6 GB of a 16 GB chip
N_REQUESTS = 8
PROMPT = (64, 128)      # ragged prompt lengths; the engine pads to the max
MAX_NEW = 16
SLOTS = 4
DECODE_CHUNK = 4
INPUT_SCALE = 0.1       # fixed DAC range: engine == static_generate bit-wise

# (what, B, K, N, stacked): granite_8b's FFN projections at tile_rows 512
KERNEL_CASES = [
    ("w_up, decode batch", 8, 4096, 14336, False),
    ("w_down, prefill batch", 128, 14336, 4096, False),
    ("w_gate|w_up gate stack, decode batch", 8, 4096, 14336, True),
]

FAILURES: list[str] = []


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(name: str, ok: bool, detail: str = "") -> bool:
    line = f"{'PASS' if ok else 'FAIL'} {name}" + (f": {detail}" if detail
                                                   else "")
    log(line)
    if not ok:
        FAILURES.append(name)
        print(f"[chip_smoke] {line}", file=sys.stderr, flush=True)
    return ok


# ---------------------------------------------------------------------------
# (a) kernel vs oracle on the chip
# ---------------------------------------------------------------------------

def kernel_agreement(y_k, y_r, s_w, lsb):
    """(ok, detail) for compiled kernel output `y_k` vs oracle `y_r`.

    The kernel scales each row block and accumulates the KB partial sums in
    order (`aimc_mvm.py` `_mac_adc_contrib`); the oracle sums the KB blocks
    first and scales once (`ref.py`). Both are exact in the int8 MAC, so
    they differ by f32 rounding: at most ~(KB + 2) ulps of the largest sum
    a column can reach, 127 codes x sum_k s_w[k] x `lsb` (`lsb` =
    adc_step x s_x). Division (`acc / adc_step`) and, with noise on, log and
    cos are not rounded identically by Mosaic and XLA, so an ADC code can
    flip where the bit-line sum sits within ulps of a rounding boundary:
    such outputs may differ by one ADC code of their largest row block, and
    at most 0.1% of outputs may."""
    import jax.numpy as jnp
    kb = s_w.shape[-2]
    eps = float(jnp.finfo(jnp.float32).eps)
    mag = 127.0 * lsb * jnp.sum(s_w, axis=-2)           # [..., Np]
    code = lsb * jnp.max(s_w, axis=-2)
    tol = 4.0 * (kb + 2) * eps * mag
    n = s_w.shape[-1]
    mag, code, tol = (v[..., None, :n] for v in (mag, code, tol))
    diff = jnp.abs(y_k - y_r)
    flipped = diff > tol
    worst = float(jnp.max(diff / jnp.maximum(tol, 1e-30)))
    share = float(jnp.mean(flipped))
    ok = bool(jnp.all(diff <= tol + 1.01 * code)) and share <= 1e-3
    bit_equal = bool(jnp.all(y_k == y_r))
    return ok, (f"bit-equal={bit_equal} max|diff|/round_tol={worst:.3g} "
                f"code-flip share={share:.2e}")


def kernel_cases(seed: int):
    """(label, x, state, s_x, front door) for each of `KERNEL_CASES`, with
    random inputs and programmed weights from `seed`."""
    import jax
    import jax.numpy as jnp

    from repro.core.aimc import AimcConfig, program_linear, stack_states
    from repro.core.quant import sym_scale
    from repro.kernels import ops

    key = jax.random.PRNGKey(seed)
    for what, b, k, n, stacked in KERNEL_CASES:
        key, kx, kw1, kw2 = jax.random.split(key, 4)
        x = jax.random.normal(kx, (b, k), jnp.float32)
        sts = [program_linear(jax.random.normal(kk, (k, n)) * k ** -0.5,
                              AimcConfig())
               for kk in ((kw1, kw2) if stacked else (kw1,))]
        st = stack_states(sts) if stacked else sts[0]
        fn = ops.aimc_matmul_stacked if stacked else ops.aimc_matmul_v2
        yield (f"{what}, B={b} K={k} N={n}", x, st,
               sym_scale(x).reshape(1, 1), fn)


def read_sigma() -> float:
    from repro.core.aimc import AimcConfig
    from repro.core.noise import NoiseModel, read_sigma_lsb
    return read_sigma_lsb(AimcConfig().tile_rows, NoiseModel(sigma_read=0.005))


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.aimc import AimcConfig

    cfg = AimcConfig()
    for what, x, st, s_x, fn in kernel_cases(seed):
        lsb = cfg.adc_step * s_x[0, 0]
        for sigma in (0.0, read_sigma()):
            seed_arr = jnp.uint32(seed + 7) if sigma else None

            def run(impl, sigma=sigma, seed_arr=seed_arr, fn=fn, s_x=s_x):
                f = jax.jit(lambda xv, w_q, s_w: fn(
                    xv, w_q, s_w, s_x, seed_arr, adc_step=cfg.adc_step,
                    sigma=sigma, impl=impl))
                t0 = time.perf_counter()
                y = jax.block_until_ready(f(x, st.w_q, st.s_w))
                return y, time.perf_counter() - t0

            y_k, t_k = run("auto")
            y_r, _ = run("ref")
            ok, detail = kernel_agreement(y_k, y_r, st.s_w, lsb)
            check(f"(a) kernel == oracle within tolerance [{what}, "
                  f"noise {'on' if sigma else 'off'}]",
                  ok, f"{detail}; compile+first call {t_k:.2f}s")


# ---------------------------------------------------------------------------
# (b)/(c) serving through the engine
# ---------------------------------------------------------------------------

def granite(depth: int):
    from repro.configs import get_arch
    spec = get_arch("granite_8b")
    full = spec.model_cfg
    cfg = dataclasses.replace(full, n_layers=depth)
    log(f"model granite_8b ({spec.source}): d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}:{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab {cfg.vocab}"
        f"; depth cut to {depth} of {full.n_layers} layers (reduced: "
        f"n_layers), random weights")
    return spec, cfg


def trace(cfg, seed: int):
    from repro.runtime.batcher import poisson_trace
    return poisson_trace(N_REQUESTS, rate=200.0, seed=seed, prompt_len=PROMPT,
                         max_new=(MAX_NEW, MAX_NEW), vocab=cfg.vocab)


def engine_kw(spec):
    import jax.numpy as jnp
    return dict(n_slots=SLOTS, prompt_pad=PROMPT[1],
                max_seq=PROMPT[1] + MAX_NEW, cache_dtype=jnp.float32,
                family=spec.family, module=spec.module,
                decode_chunk=DECODE_CHUNK)


def program(params, n_contexts: int, seed: int):
    import jax

    from repro.core.aimc import AimcConfig
    from repro.core.program import MappingPlan, program_model
    aimc = AimcConfig(input_scale=INPUT_SCALE)
    t0 = time.perf_counter()
    prog = program_model(params, MappingPlan(n_contexts=n_contexts), aimc,
                         jax.random.PRNGKey(seed + 2))
    installed = prog.install(params)
    jax.block_until_ready([st.w_q for st in prog.states])
    log(f"programmed in {time.perf_counter() - t0:.2f}s: {prog.summary()}")
    return aimc, prog, installed


def served_all(report, reqs, label: str) -> None:
    done = [rid for rid, rec in report.records.items()
            if len(rec.tokens) == MAX_NEW]
    check(f"{label}: every request served ({len(reqs)} requests, "
          f"{MAX_NEW} tokens each)", len(done) == len(reqs),
          f"{len(done)}/{len(reqs)} complete, {report.generated_tokens} "
          f"tokens, {report.n_steps} decode steps")


def warm(eng, label: str) -> dict:
    t0 = time.perf_counter()
    counts = eng.warmup()
    log(f"{label}: warmup compiled {counts} in "
        f"{time.perf_counter() - t0:.2f}s")
    return counts


def first_diffs(report, reqs, want_rows) -> dict:
    """rid -> (batch row, index of the first token where the engine and the
    static loop part, the engine's token there) for every request where
    they do."""
    out = {}
    for row, (r, want) in enumerate(zip(reqs, want_rows)):
        want, got = [int(t) for t in want], report.tokens(r.rid)
        if got != want:
            i = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
                     min(len(got), len(want)))
            out[r.rid] = (row, i, got[i] if i < len(got) else None)
    return out


def static_prefill(model, cfg, exe, params, prompts, max_seq: int,
                   split: bool):
    """`static_generate`'s prefill closure on `prompts` as one batch or, with
    `split`, on each prompt alone (`[1, P]`, the engine's prefill shape),
    the results joined along their batch axes: (logits, cache)."""
    import jax
    import jax.numpy as jnp

    from repro.runtime.engine import _static_closures
    prefill, _ = _static_closures(model, cfg, exe, max_seq, jnp.float32)
    if not split:
        return prefill(params, prompts)
    rows = [prefill(params, prompts[i:i + 1]) for i in range(len(prompts))]
    two = jax.eval_shape(prefill, params, jnp.concatenate([prompts[:1]] * 2))
    axes = jax.tree.map(lambda a, b: next(
        i for i, (m, n) in enumerate(zip(a.shape, b.shape)) if m != n),
        rows[0], two)
    return jax.tree.map(lambda ax, *xs: jnp.concatenate(xs, ax), axes, *rows)


def lockstep(model, cfg, exe, params, prompts, max_seq: int, split: bool):
    """`static_generate`'s greedy loop after `static_prefill`, keeping every
    step's logits: (tokens [B, MAX_NEW], per-step [B, V] logits). Without
    `split` it is `static_generate`; with it, the rows decode together
    after prefills shaped as the engine's."""
    import jax.numpy as jnp

    from repro.runtime.engine import _static_closures
    _, decode = _static_closures(model, cfg, exe, max_seq, jnp.float32)
    logits, cache = static_prefill(model, cfg, exe, params, prompts, max_seq,
                                   split)
    steps = [logits[:, -1]]
    toks = [jnp.argmax(steps[-1], -1).astype(jnp.int32)[:, None]]
    for _ in range(MAX_NEW - 1):
        logits, cache = decode(params, cache, toks[-1])
        steps.append(logits[:, -1])
        toks.append(jnp.argmax(steps[-1], -1).astype(jnp.int32)[:, None])
    return jnp.concatenate(toks, axis=1), steps


def margins(diffs: dict, want, steps) -> str:
    """Where the engine parted from the reference: the reference's logits at
    that step as (reference token's logit - engine token's logit) /
    max |logit|. Near f32 epsilon is a near-tie that rounding can turn; a
    large value is a wrong token or a diverged history."""
    import jax.numpy as jnp
    out = []
    for rid, (row, i, got) in diffs.items():
        if got is None:
            out.append(f"rid {rid}: stopped at token {i}")
            continue
        lg, ref = steps[i][row], int(want[row][i])
        rel = float((lg[ref] - lg[got]) / jnp.max(jnp.abs(lg)))
        out.append(f"rid {rid} token {i}: reference {ref}, engine {got}, "
                   f"relative logit margin {rel:.3e}")
    return "; ".join(out)


def max_gap(a, b) -> float:
    import jax
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def phase_aimc_serving(spec, cfg, model, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models.layers import Execution
    from repro.runtime.batcher import reconcile, synchronized_trace
    from repro.runtime.engine import ServeEngine, static_generate

    aimc, prog, installed = program(params, 1, seed)
    exe = Execution(mode="aimc", aimc=aimc, compute_dtype="float32",
                    programmed=True)
    eng = ServeEngine(model, cfg, exe, installed, program=prog,
                      **engine_kw(spec))
    counts = warm(eng, "(b) aimc")
    reqs = trace(cfg, seed)
    report = eng.serve(list(reqs))
    served_all(report, reqs, "(b) aimc")
    led_sum, static = reconcile(prog, report.records, report.observed_vectors)
    check("(b) CM_* ledgers reconcile with program.mvm_counts()",
          report.observed_vectors == report.useful_vectors
          and led_sum == static
          and static == prog.mvm_counts().scaled(report.observed_vectors),
          f"{report.observed_vectors} vectors, CM_PROCESS "
          f"{static.process}")

    # Tokens against the static loop under the fixed DAC scale. The quantized
    # network is chaotic: an f32 rounding difference anywhere turns a DAC
    # code, that turns ADC codes, and within a few tokens the histories part.
    # So each side must run the same shapes. On the chip a prefill row's
    # result depends on how many rows the batch holds (the gap is logged
    # below), and the engine prefills one request at a time. The 4-slot
    # engine is compared with static_generate's closures prefilling each
    # prompt alone and decoding the 4 rows together, the one-slot engine with
    # static_generate itself, one request at a time.
    sync = synchronized_trace(SLOTS, prompt_len=PROMPT[1], max_new=MAX_NEW,
                              seed=seed + 1, vocab=cfg.vocab)
    max_seq = PROMPT[1] + MAX_NEW
    prompts = jnp.asarray([r.prompt for r in sync], jnp.int32)
    run = functools.partial(lockstep, model, cfg, exe, installed, prompts,
                            max_seq)
    t0 = time.perf_counter()
    rep4 = eng.serve(list(sync))
    want4, steps4 = run(split=True)
    diff4 = first_diffs(rep4, sync, want4)
    log(f"(b) {SLOTS}-slot engine + reference {time.perf_counter() - t0:.2f}s")
    check(f"(b) {SLOTS}-slot engine tokens == static_generate's closures with "
          f"each prompt prefilled alone and the {SLOTS} rows decoded together "
          f"(fixed DAC input_scale {INPUT_SCALE})", not diff4,
          margins(diff4, want4, steps4))
    check("(b) no recompile after warmup", eng.compile_counts() == counts,
          f"{eng.compile_counts()}")
    gap = max_gap(static_prefill(model, cfg, exe, installed, prompts, max_seq,
                                 split=False),
                  static_prefill(model, cfg, exe, installed, prompts, max_seq,
                                 split=True))
    whole = first_diffs(rep4, sync, run(split=False)[0])
    log(f"(b) static_generate's prefill of the same {SLOTS} prompts as one "
        f"batch and one at a time: max |difference| in logits and KV cache "
        f"{gap:.3e}; the batch-{SLOTS} static_generate parts from the engine "
        f"in {len(whole)} of {len(sync)} requests")

    solo = ServeEngine(model, cfg, exe, installed, program=prog,
                       **dict(engine_kw(spec), n_slots=1))
    warm(solo, "(b) aimc, one slot")
    rep1 = solo.serve(list(sync))
    t0 = time.perf_counter()
    gen1 = [static_generate(model, cfg, exe, installed, prompts[i:i + 1],
                            MAX_NEW, max_seq=max_seq)[0][0]
            for i in range(len(sync))]
    log(f"(b) batch-1 static_generate (compile + run) "
        f"{time.perf_counter() - t0:.2f}s")
    diff1 = []
    for i, (r, want) in enumerate(zip(sync, gen1)):
        d = first_diffs(rep1, [r], [want])
        if d:
            diff1.append(margins(d, [want], lockstep(
                model, cfg, exe, installed, prompts[i:i + 1], max_seq,
                split=False)[1]))
    check(f"(b) one-slot engine tokens == batch-1 static_generate "
          f"({len(sync)} requests, one at a time)", not diff1,
          "; ".join(diff1))

    n = eng.decode_chunk
    t0 = time.perf_counter()
    hlo = eng._decode_jits[n].lower(
        eng.params, eng._empty_cache(), eng._empty_tok_buf(),
        eng._empty_state()).compile().as_text()
    check(f"(b) compiled decode closure (chunk {n}) holds the Mosaic kernel",
          "tpu_custom_call" in hlo,
          f"{hlo.count('tpu_custom_call')} tpu_custom_call sites "
          f"({time.perf_counter() - t0:.2f}s to fetch)")
    del eng, solo, installed, prog
    jax.clear_caches()


def phase_digital_serving(spec, cfg, model, params, seed: int) -> None:
    from repro.models.layers import Execution
    from repro.runtime.engine import ServeEngine

    eng = ServeEngine(model, cfg, Execution(compute_dtype="bfloat16"),
                      params, **engine_kw(spec))
    counts = warm(eng, "(c) digital bf16")
    reqs = trace(cfg, seed)
    report = eng.serve(list(reqs))
    served_all(report, reqs, "(c) digital bf16")
    check("(c) no recompile after warmup", eng.compile_counts() == counts,
          f"{eng.compile_counts()}")


def one_chip(seed: int) -> None:
    import jax
    phase_kernels(seed)
    spec, cfg = granite(DEPTH)
    model = spec.model_module()
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed), cfg))
    phase_aimc_serving(spec, cfg, model, params, seed)
    phase_digital_serving(spec, cfg, model, params, seed)


# ---------------------------------------------------------------------------
# --four-chips: sharded engine vs single-device engine
# ---------------------------------------------------------------------------

def sharded_kernels(mesh, seed: int) -> None:
    """The served kernel dispatch on `mesh` (input rows split over `data`,
    bit lines over `model`, counter read noise on) against the same call on
    one device. Rows and columns are independent, the MAC is exact and each
    shard draws the noise of its global rows and columns, so the two agree
    bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.aimc import AimcConfig
    adc, sigma = AimcConfig().adc_step, read_sigma()
    for what, x, st, s_x, fn in kernel_cases(seed):
        f = jax.jit(lambda xv, fn=fn, st=st, s_x=s_x: fn(
            xv, st.w_q, st.s_w, s_x, jnp.uint32(seed + 7), adc_step=adc,
            sigma=sigma))
        one = np.asarray(f(x))
        with jax.set_mesh(mesh):
            on_mesh = np.asarray(f(x))
        check(f"sharded kernel dispatch == one device, bit for bit [{what}, "
              f"noise on]", bool((one == on_mesh).all()),
              f"max |difference| {np.abs(one - on_mesh).max():.3e}")


def engines(model, cfg, exe, params, mesh, kw, label: str, seed: int):
    """Single-device and sharded engines over the same weights, both warmed
    up, serving the same trace: (sharded engine, its warmup counts, trace,
    single-device report, sharded report)."""
    from repro.runtime.engine import ServeEngine, ShardedServeEngine
    single = ServeEngine(model, cfg, exe, params, **kw)
    warm(single, f"{label} single-device")
    sharded = ShardedServeEngine(model, cfg, exe, params, mesh=mesh, **kw)
    counts = warm(sharded, f"{label} sharded")
    reqs = trace(cfg, seed)
    return sharded, counts, reqs, single.serve(list(reqs)), \
        sharded.serve(list(reqs))


def four_chips(seed: int) -> None:
    """`ShardedServeEngine` on data:2,model:2 against the single-device
    engine. What is bit-equal across the mesh is checked bit for bit: the
    kernel dispatch, and the tokens of the digital float32 model. AIMC tokens
    are logged, not checked: per-chip shapes differ from one chip's (2 slot
    rows, half the heads), the chip's f32 rounding follows the shape, and the
    quantized layers turn an ulp into a different token history (PERF.md,
    PR 11)."""
    import jax

    from repro.core import isa
    from repro.core.schedule import CoreSchedule
    from repro.launch.mesh import make_mesh
    from repro.models.layers import Execution
    from repro.runtime.batcher import reconcile, reconcile_cores

    mesh = make_mesh((2, 2), ("data", "model"))
    log(f"mesh data:2,model:2 over {mesh.devices.size} devices")
    sharded_kernels(mesh, seed)

    spec, cfg = granite(DEPTH)
    model = spec.model_module()
    params = model.init(jax.random.PRNGKey(seed), cfg)
    aimc, prog, installed = program(params, 2, seed)
    del params
    sched = CoreSchedule.from_program(prog)
    exe = Execution(mode="aimc", aimc=aimc, compute_dtype="float32",
                    programmed=True)
    sharded, counts, reqs, r1, r2 = engines(
        model, cfg, exe, installed, mesh,
        dict(engine_kw(spec), program=prog, schedule=sched), "aimc", seed)
    served_all(r2, reqs, "sharded aimc")
    parted = {r.rid: next(i for i, (a, b) in enumerate(
        zip(r1.tokens(r.rid), r2.tokens(r.rid))) if a != b)
        for r in reqs if r1.tokens(r.rid) != r2.tokens(r.rid)}
    log(f"sharded aimc tokens equal the single-device engine's in "
        f"{len(reqs) - len(parted)} of {len(reqs)} requests; first differing "
        f"token of the others {parted}")
    check("sharded aimc: no recompile after warmup",
          sharded.compile_counts() == counts, f"{sharded.compile_counts()}")
    led, static = reconcile(prog, r2.records, r2.observed_vectors)
    cores, core_static = reconcile_cores(sched, r2.records,
                                         r2.observed_vectors)
    devs = sharded.device_ledgers(r2)
    total = prog.mvm_counts().scaled(r2.observed_vectors)
    dev_sum = isa.total(devs.values())
    check("sharded aimc: per-request, per-core and per-device CM_* ledgers "
          "reconcile with program.mvm_counts()",
          r2.observed_vectors == r2.useful_vectors and led == static == total
          and cores == core_static == total and dev_sum == total,
          f"{len(devs)} model-axis devices, CM_PROCESS {total.process}")
    del sharded, installed, prog
    jax.clear_caches()

    params = model.init(jax.random.PRNGKey(seed), cfg)
    sharded, counts, reqs, r1, r2 = engines(
        model, cfg, Execution(compute_dtype="float32"), params, mesh,
        engine_kw(spec), "digital f32", seed)
    served_all(r2, reqs, "sharded digital f32")
    same = [r.rid for r in reqs if r1.tokens(r.rid) == r2.tokens(r.rid)]
    check("sharded digital f32 tokens bit-equal to the single-device engine",
          len(same) == len(reqs), f"{len(same)}/{len(reqs)} requests equal")
    check("sharded digital f32: no recompile after warmup",
          sharded.compile_counts() == counts, f"{sharded.compile_counts()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded data:2,model:2 phase "
                         "(needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    want = 4 if args.four_chips else 1
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX's first device is {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < want:
        print(f"[chip_smoke] needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = Path(enable_compile_cache())
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    log(f"compile cache {cache} ({n_cached} entries at start)")

    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(args.seed)
    log(f"all phases {time.perf_counter() - t0:.2f}s; peak device memory "
        f"{dev.memory_stats().get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")
    if FAILURES:
        log(f"{len(FAILURES)} check(s) failed: {FAILURES}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
