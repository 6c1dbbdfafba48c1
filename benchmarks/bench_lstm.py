"""Paper Fig. 10 + Fig. 11 — LSTM exploration (§VIII).

PTB character LSTM, n_h in {256, 512, 750}, digital 1/2/5-core references vs
AIMC cases 1-4. Checks (§VIII headline claims):
  * n_h=750 speedup up to 9.4x / energy 9.3x (high-power),
  * n_h=256 gains only 1.0-1.5x (working set already fits caches),
  * multi-core case 4 is ~10% FASTER than case 1 (unlike the MLP),
  * AIMC run time grows sub-quadratically with n_h (~1.4x avg step),
  * cell dequeue+activation dominates the analog run time (Fig. 11).
"""

from __future__ import annotations

import time

from benchmarks.common import Check, fmt_e, fmt_t, table
from repro.core.costmodel import HIGH_POWER, LOW_POWER, evaluate, speedup
from repro.core.workloads import lstm_workloads

NHS = (256, 512, 750)
CASES = ["dig_1c", "dig_2c", "dig_5c",
         "ana_case1", "ana_case2", "ana_case3", "ana_case4"]


def run(verbose: bool = True) -> dict:
    results = {}
    for sysc in (HIGH_POWER, LOW_POWER):
        res = {}
        for nh in NHS:
            w = lstm_workloads(nh)
            res[nh] = {c: evaluate(w[c], sysc) for c in CASES}
        results[sysc.name] = res
        if verbose:
            rows = []
            for nh in NHS:
                dig = res[nh]["dig_1c"]
                for c in CASES:
                    r = res[nh][c]
                    s, e = speedup(dig, r)
                    rows.append([nh, c, fmt_t(r.time_s), fmt_e(r.energy_j),
                                 f"{s:.1f}x", f"{e:.1f}x"])
            print(table(f"LSTM — {sysc.name} system (Fig. 10)",
                        ["n_h", "case", "time/inf", "energy/inf",
                         "speedup", "energy gain"], rows))
            print()
    if verbose:
        rows = []
        res = results["high-power"]
        for nh in NHS:
            for case in ("ana_case1", "ana_case4"):
                r = res[nh][case]
                tot = sum(r.breakdown.values()) or 1.0
                deq_act = (r.breakdown["analog_dequeue"]
                           + r.breakdown["digital_ops"]) / tot
                q = r.breakdown["analog_queue"] / tot
                rows.append([nh, case, f"{deq_act:.0%}", f"{q:.0%}"])
        print(table("LSTM sub-ROI shares, high-power (Fig. 11)",
                    ["n_h", "case", "dequeue+activation", "queue"], rows))
        print()
    return results


def checks(results=None) -> list[Check]:
    results = results or run(verbose=False)
    hp = results["high-power"]
    s750, e750 = speedup(hp[750]["dig_1c"], hp[750]["ana_case1"])
    s256, _ = speedup(hp[256]["dig_1c"], hp[256]["ana_case1"])
    # analog run-time growth with n_h (paper: ~1.4x average step)
    t256 = hp[256]["ana_case1"].time_s
    t512 = hp[512]["ana_case1"].time_s
    t750 = hp[750]["ana_case1"].time_s
    growth = ((t512 / t256) + (t750 / t512)) / 2
    r = hp[750]["ana_case1"].breakdown
    share = ((r["analog_dequeue"] + r["digital_ops"])
             / (sum(r.breakdown.values()) if hasattr(r, "breakdown")
                else sum(r.values())))
    return [
        Check("LSTM n_h=750 speedup (high-power)", s750, 9.4),
        Check("LSTM n_h=750 energy gain (high-power)", e750, 9.3),
        Check("LSTM n_h=256 speedup (1.0-1.5x band)", s256, 1.5, rtol=0.45),
        Check("analog run-time growth per size step (~1.4x)", growth, 1.4,
              rtol=0.3),
        Check("case4 ~10% faster than case1 (n_h=750)",
              hp[750]["ana_case1"].time_s / hp[750]["ana_case4"].time_s,
              1.10, rtol=0.15),
        Check("cell dequeue+activation dominates (<=81.8%)", share, 0.75,
              rtol=0.3),
    ]


def run_wallclock(nh: int = 750, steps: int = 16, batch: int = 8,
                  iters: int = 5, verbose: bool = True) -> dict:
    """Measured program-once vs per-call-reprogram decode on the PTB LSTM.

    One decode step == one jitted call, mirroring the serving loop: the
    programmed path holds the four gate matrices stationary (side-by-side
    tenant, §VIII-D — programmed ONCE before the loop); the reprogram path
    re-quantizes + re-programs the cell weights on EVERY step (what
    `serve --exec aimc` paid per token before the program API)."""
    import jax
    import jax.numpy as jnp

    from repro.core.aimc import (AimcConfig, aimc_apply, aimc_linear_ste,
                                 program_linear)
    from repro.models.paper_nets import _lstm_cell_math, lstm_init

    cfg = AimcConfig(tile_rows=512)
    params = lstm_init(jax.random.PRNGKey(0), nh)
    w_cell = jnp.concatenate([params["w_f"], params["w_i"], params["w_g"],
                              params["w_o"]], axis=1)
    xs = jax.random.normal(jax.random.PRNGKey(1), (steps, batch, 50))

    st_cell = program_linear(w_cell, cfg)       # CM_INITIALIZE, once
    st_y = program_linear(params["w_y"], cfg)

    @jax.jit
    def step_programmed(st_cell, st_y, h, c, x_t):
        gates = aimc_apply(st_cell, jnp.concatenate([h, x_t], -1), cfg)
        h, c = _lstm_cell_math(gates, c, nh)
        return h, c, jax.nn.softmax(aimc_apply(st_y, h, cfg), -1)

    @jax.jit
    def step_reprogram(w_cell, w_y, h, c, x_t):
        gates = aimc_linear_ste(jnp.concatenate([h, x_t], -1), w_cell, None,
                                cfg)
        h, c = _lstm_cell_math(gates, c, nh)
        return h, c, jax.nn.softmax(aimc_linear_ste(h, w_y, None, cfg), -1)

    def _loop(step, *weights):
        h = jnp.zeros((batch, nh))
        c = jnp.zeros((batch, nh))
        for t in range(steps):
            h, c, y = step(*weights, h, c, xs[t])
        return y

    def _time(step, *weights):
        jax.block_until_ready(_loop(step, *weights))    # compile + warm
        t0 = time.time()
        for _ in range(iters):
            out = _loop(step, *weights)
        jax.block_until_ready(out)
        return (time.time() - t0) / (iters * steps)

    t_prog = _time(step_programmed, st_cell, st_y)
    t_reprog = _time(step_reprogram, w_cell, params["w_y"])
    out = {"t_programmed": t_prog, "t_reprogram": t_reprog,
           "speedup": t_reprog / t_prog}
    if verbose:
        print(table(f"LSTM n_h={nh} measured decode, batch={batch} "
                    f"(simulated crossbars, this host, per step)",
                    ["path", "time/step", "vs reprogram"],
                    [["program-once (apply)", fmt_t(t_prog),
                      f"{out['speedup']:.2f}x"],
                     ["per-step reprogram (seed)", fmt_t(t_reprog), "1.0x"]]))
        print()
    return out


if __name__ == "__main__":
    res = run()
    run_wallclock()
    for c in checks(res):
        print(c.row())
