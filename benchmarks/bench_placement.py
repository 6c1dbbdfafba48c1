"""Auto-placement: predicted-vs-measured for the cost-model placer.

The placer (`core.placement`, DESIGN.md §16) decides analog vs digital per
layer from per-layer one-stage workloads priced by `costmodel.evaluate`.
That decomposition is only trustworthy if (a) the per-layer sums agree
EXACTLY with the monolithic model on the combined split workload, (b) the
analog side agrees EXACTLY with the independent `core.schedule` pricing of
the program the plan actually builds, and (c) the modeled digital times
RANK real layers correctly — checked by measuring per-layer digital MVM
wallclock on this host and fitting the affine `PlacementRoofline`
(measured = t_fixed + scale * modeled, the `OverlapRoofline` idiom), then
gating the per-layer relative residuals. The analog side has no silicon
under it, so it is consistency-gated (a)+(b) only — the same
modeled-latency bar the paper's own Table I numbers live on.

Gates:
  * per-layer sum / evaluate(split_workload) == 1.000 for all-digital,
    the chosen split, and all-analog (exact-by-construction; rtol 1%)
  * analog per-layer sum / CoreSchedule.from_program modeled latency
    == 1.000 on the chosen plan's program (rtol 1%)
  * affine roofline fit over measured digital per-layer wallclock:
    every relative residual <= 0.75 for layers above dispatch scale
    (< 50us measured is recorded but ungated — see the inline note)
  * predicted latency is monotone non-increasing in the tile budget
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import Check, fmt_t, table
from repro.configs import get_arch
from repro.core.aimc import AimcConfig
from repro.core.costmodel import (CALIB, HIGH_POWER, digital_mvm_stage,
                                  evaluate, split_workload)
from repro.core.placement import (PlacementRoofline, layer_costs,
                                  plan_placement)
from repro.core.program import MappingPlan, program_model
from repro.core.schedule import CoreSchedule

# synthetic digital-measurement layer set: enough size spread for the
# affine fit to see the modeled time, big enough that one apply is not
# pure dispatch overhead
MEASURE_SHAPES = [(256, 256), (512, 512), (1024, 1024),
                  (1024, 4096), (2048, 2048)]
BUDGETS = (1, 2, 3, 4, 6, 8, 0)   # 0 = uncapped


def _wallclock(fn, *args, reps: int = 20) -> float:
    jax.block_until_ready(fn(*args))          # compile outside the timing
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(verbose: bool = True) -> dict:
    out: dict = {}
    spec = get_arch("granite-8b")
    cfg_model = spec.smoke_cfg
    model = spec.model_module()
    params = model.init(jax.random.PRNGKey(0), cfg_model)
    acfg = AimcConfig(adc_alpha=0.5, tile_rows=64)

    # ---- gate (a): per-layer sums == evaluate() on the split workload ----
    res = plan_placement(params, MappingPlan(), acfg, tiles_per_context=None,
                         n_contexts=1)
    layers = [(c.path, c.k, c.n, c.instances) for c in res.costs]
    all_paths = tuple(c.path for c in res.costs)
    rows, eval_ratios = [], []
    for name, analog in [("all_digital", ()), ("chosen", res.analog),
                         ("all_analog", all_paths)]:
        wl = split_workload(name, layers, analog, tile_rows=acfg.tile_rows)
        t_eval = evaluate(wl, HIGH_POWER, CALIB).time_s
        t_sum = res.predicted_for(analog)
        eval_ratios.append((name, t_sum / t_eval))
        rows.append([name, len(analog), fmt_t(t_sum), fmt_t(t_eval),
                     f"{t_sum / t_eval:.4f}"])
    out["eval_ratios"] = eval_ratios
    if verbose:
        print(table("placer per-layer sums vs costmodel.evaluate "
                    "(one token vector)",
                    ["split", "analog", "sum", "evaluate", "ratio"], rows))
        print()

    # ---- gate (b): analog sum == schedule pricing of the real program ----
    prog = program_model(params, res.plan, acfg, jax.random.PRNGKey(2))
    sched = CoreSchedule.from_program(prog)
    t_sched = sched.modeled_latency(HIGH_POWER, CALIB)
    analog_set = set(res.analog)
    t_analog = sum(c.t_analog for c in res.costs if c.path in analog_set)
    out["sched_ratio"] = t_analog / t_sched
    if verbose:
        print(f"  analog per-layer sum {fmt_t(t_analog)} vs "
              f"CoreSchedule.from_program {fmt_t(t_sched)} "
              f"(ratio {out['sched_ratio']:.4f})")
        print()

    # ---- budget sweep: predicted latency monotone in the budget ----------
    rows, sweep = [], []
    for b in BUDGETS:
        r = plan_placement(params, MappingPlan(), acfg,
                           tiles_per_context=b or None, n_contexts=1)
        sweep.append((b, r.predicted_s))
        rows.append([b or "inf", len(r.analog), f"{r.overflow}",
                     fmt_t(r.predicted_s),
                     f"{r.predicted_digital_s / r.predicted_s:.2f}x"])
    capped = [t for _, t in sweep[:-1]]   # BUDGETS ends with uncapped
    out["budget_sweep"] = sweep
    out["monotone"] = all(a >= b - 1e-15 for a, b in zip(capped, capped[1:]))
    out["dominates_digital"] = all(
        t <= res.predicted_digital_s + 1e-15 for _, t in sweep)
    if verbose:
        print(table("budget sweep (predicted latency must not worsen with "
                    "more budget)",
                    ["budget", "analog", "overflow", "predicted",
                     "vs digital"], rows))
        print()

    # ---- gate (c): measured digital wallclock vs modeled (roofline) ------
    modeled, measured, rows = [], [], []
    for k, n in MEASURE_SHAPES:
        w = jax.random.normal(jax.random.PRNGKey(hash((k, n)) % 2**31),
                              (k, n), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, k), jnp.float32)
        fwd = jax.jit(lambda v, w=w: v @ w)
        t_meas = _wallclock(fwd, x)
        wl_t = evaluate(
            split_workload(f"dig_{k}x{n}", [(f"m{k}x{n}", k, n, 1)], (),
                           tile_rows=acfg.tile_rows),
            HIGH_POWER, CALIB).time_s
        modeled.append(wl_t)
        measured.append(t_meas)
    fit = PlacementRoofline.fit(modeled, measured)
    resid = fit.residuals(modeled, measured)
    # layers whose measured time sits at dispatch scale (< 50us) are
    # recorded but NOT gated: a ~10-20us wallclock swings 2x with run
    # context (JIT cache/CPU state), and the affine fit's fixed term is
    # anchored by the ms-scale layers — gating the noise would make the
    # whole suite flaky. Logged per the no-silent-caps rule.
    gated = [r for tw, r in zip(measured, resid) if tw >= 50e-6]
    dropped = [f"{k}x{n}" for (k, n), tw in zip(MEASURE_SHAPES, measured)
               if tw < 50e-6]
    for (k, n), tm, tw, r in zip(MEASURE_SHAPES, modeled, measured, resid):
        rows.append([f"{k}x{n}", fmt_t(tm), fmt_t(tw),
                     fmt_t(fit.predict_s(tm)), f"{r:.2f}"])
    out["roofline"] = {"t_fixed_s": fit.t_fixed_s, "scale": fit.scale,
                       "residuals": resid, "gated_residuals": gated,
                       "ungated_layers": dropped}
    if verbose and dropped:
        print(f"  NOT gated (dispatch-scale, < 50us measured): "
              f"{', '.join(dropped)}")
    if verbose:
        print(table(
            f"digital per-layer wallclock vs modeled "
            f"(fit: {fit.t_fixed_s * 1e6:.1f}us + {fit.scale:.2f} x "
            f"modeled)",
            ["layer", "modeled", "measured", "fit-predicted",
             "rel-residual"], rows))
        print()
    return out


def checks(results=None) -> list[Check]:
    results = results or run(verbose=False)
    worst_eval = max(abs(r - 1.0) for _, r in results["eval_ratios"])
    roof = results["roofline"]
    worst_resid = max(roof.get("gated_residuals") or roof["residuals"])
    return [
        Check("placer per-layer sums == evaluate(split_workload)",
              1.0 + worst_eval, 1.0, rtol=0.01),
        Check("placer analog sum == schedule-modeled program latency",
              results["sched_ratio"], 1.0, rtol=0.01),
        Check("predicted latency monotone non-worsening in budget",
              1.0 if results["monotone"] else 0.0, 1.0, rtol=0.01),
        Check("chosen split never worse than all-digital",
              1.0 if results["dominates_digital"] else 0.0, 1.0, rtol=0.01),
        Check("measured digital wallclock within roofline fit "
              "(max rel residual <= 0.75)",
              1.0 if worst_resid <= 0.75 else 0.0, 1.0, rtol=0.01),
    ]


if __name__ == "__main__":
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH",
                    help="write results + checks as JSON")
    args = ap.parse_args()
    res = run()
    cs = checks(res)
    for c in cs:
        print(c.row())
    if args.json:
        payload = {"results": {k: v for k, v in res.items()},
                   "checks": [{"name": c.name, "measured": c.measured,
                               "target": c.target, "ok": c.ok} for c in cs]}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")
    sys.exit(0 if all(c.ok for c in cs) else 1)
