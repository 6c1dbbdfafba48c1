"""Paper Fig. 7 + Fig. 8 — multi-layer perceptron exploration (§VII).

Reproduces, per system configuration (high-power / low-power):
  * total time, energy and memory intensity for the digital 1/2/4-core
    references and AIMC cases 1-4 (Fig. 7);
  * the sub-ROI run-time breakdown of the analog cases (Fig. 8);
  * the paper's §VII headline claims, checked in `checks()`:
      - max speedup 12.8x / energy 12.5x (high-power, case 1),
      - case 1 beats case 2 by a slight margin,
      - multi-core is SLOWER: case 1 ~20% better than case 3, ~30% than 4,
      - low-power gains are smaller than high-power gains.
"""

from __future__ import annotations

import time

from benchmarks.common import Check, fmt_e, fmt_t, table
from repro.core.costmodel import CALIB, HIGH_POWER, LOW_POWER, evaluate, speedup
from repro.core.workloads import mlp_workloads

CASES = ["dig_1c", "dig_2c", "dig_4c",
         "ana_case1", "ana_case2", "ana_case3", "ana_case4"]


def run(verbose: bool = True) -> dict:
    w = mlp_workloads()
    results = {}
    for sysc in (HIGH_POWER, LOW_POWER):
        res = {c: evaluate(w[c], sysc) for c in CASES}
        results[sysc.name] = res
        if verbose:
            rows = []
            dig = res["dig_1c"]
            for c in CASES:
                r = res[c]
                s, e = speedup(dig, r)
                rows.append([c, fmt_t(r.time_s), fmt_e(r.energy_j),
                             f"{r.llc_mpi * 1e3:.3f}", f"{s:.1f}x", f"{e:.1f}x"])
            print(table(f"MLP (1024,1024) — {sysc.name} system (Fig. 7)",
                        ["case", "time/inf", "energy/inf", "LLCMPI(e-3)",
                         "speedup", "energy gain"], rows))
            print()
    # Fig. 8 — sub-ROI breakdown, averaged across systems, analog case 1
    if verbose:
        rows = []
        for case in ("dig_1c", "ana_case1", "ana_case3", "ana_case4"):
            shares = {}
            for sysc in (HIGH_POWER, LOW_POWER):
                r = results[sysc.name][case]
                tot = sum(r.breakdown.values()) or 1.0
                for k, v in r.breakdown.items():
                    shares[k] = shares.get(k, 0.0) + v / tot / 2
            top = sorted(shares.items(), key=lambda kv: -kv[1])[:4]
            rows.append([case] + [f"{k}={v:.0%}" for k, v in top])
        print(table("MLP sub-ROI time shares (Fig. 8)",
                    ["case", "1st", "2nd", "3rd", "4th"], rows))
        print()
    return results


def checks(results=None) -> list[Check]:
    results = results or run(verbose=False)
    hp, lp = results["high-power"], results["low-power"]
    s1, e1 = speedup(hp["dig_1c"], hp["ana_case1"])
    s1l, _ = speedup(lp["dig_1c"], lp["ana_case1"])
    out = [
        Check("MLP max speedup (high-power, case 1)", s1, 12.8),
        Check("MLP max energy gain (high-power, case 1)", e1, 12.5),
        Check("case1 vs case3 run-time advantage (~20%)",
              hp["ana_case3"].time_s / hp["ana_case1"].time_s, 1.20),
        Check("case1 vs case4 run-time advantage (~30%)",
              hp["ana_case4"].time_s / hp["ana_case1"].time_s, 1.30, rtol=0.2),
        Check("case1 beats case2 (slight margin)",
              hp["ana_case2"].time_s / hp["ana_case1"].time_s, 1.2, rtol=0.25),
    ]
    # qualitative: low-power gains < high-power gains
    out.append(Check("low-power gain < high-power gain (ratio)",
                     s1l / s1, 0.65, rtol=0.35))
    return out


def run_wallclock(batch: int = 8, iters: int = 30, verbose: bool = True) -> dict:
    """Measured (not analytical) program-once vs per-call-reprogram timings.

    The simulated-crossbar MLP forward, jitted, on this host: the programmed
    path applies pre-initialized `AimcLinearState`s (the paper's deployment
    model, `core.program`); the reprogram path quantizes + programs both
    weight matrices inside every call (the pre-API behaviour of the model
    zoo's `aimc_linear_ste` hot path)."""
    import jax
    import jax.numpy as jnp

    from repro.core.aimc import (AimcConfig, aimc_apply, aimc_linear_ste,
                                 program_linear)
    from repro.models.paper_nets import mlp_init

    cfg = AimcConfig(tile_rows=512)
    params = mlp_init(jax.random.PRNGKey(0), 1024)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 1024))

    s1 = program_linear(params["w1"], cfg)      # CM_INITIALIZE, once
    s2 = program_linear(params["w2"], cfg)

    @jax.jit
    def programmed(s1, s2, x):
        h = jax.nn.relu(aimc_apply(s1, x, cfg))
        return jax.nn.relu(aimc_apply(s2, h, cfg))

    @jax.jit
    def reprogram(p, x):
        h = jax.nn.relu(aimc_linear_ste(x, p["w1"], None, cfg))
        return jax.nn.relu(aimc_linear_ste(h, p["w2"], None, cfg))

    def _time(fn, *args):
        jax.block_until_ready(fn(*args))        # compile + warm
        t0 = time.time()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.time() - t0) / iters

    t_prog = _time(programmed, s1, s2, x)
    t_reprog = _time(reprogram, params, x)
    out = {"t_programmed": t_prog, "t_reprogram": t_reprog,
           "speedup": t_reprog / t_prog}
    if verbose:
        print(table(f"MLP (1024,1024) measured inference, batch={batch} "
                    f"(simulated crossbars, this host)",
                    ["path", "time/call", "vs reprogram"],
                    [["program-once (apply)", fmt_t(t_prog),
                      f"{out['speedup']:.2f}x"],
                     ["per-call reprogram (seed)", fmt_t(t_reprog), "1.0x"]]))
        print()
    return out


if __name__ == "__main__":
    res = run()
    run_wallclock()
    for c in checks(res):
        print(c.row())
