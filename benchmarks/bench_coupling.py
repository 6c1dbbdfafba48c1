"""§VII-B — tight vs loose AIMC coupling, in both views.

1. Analytical (the paper's own experiment): the case-1 MLP mapping executed
   over the I/O bus ("loose") vs per-core private tiles ("tight").
   Paper: loose achieves 4.1x over the digital reference but is up to 3.1x
   slower than tight.

2. JAX/TPU view (the DESIGN.md §2 adaptation): `core.coupling.tight_forward`
   (one fused region, analog-domain intermediates never leave VMEM) vs
   `loose_forward` (optimization_barrier between DAC / crossbar / ADC /
   digital stages -> every intermediate materializes to HBM). Compared on
   HBM bytes from `cost_analysis()` of the lowered computations — the TPU
   mirror of the I/O-bus round-trips.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import Check, fmt_t, table
from repro.core.aimc import AimcConfig, program_linear
from repro.core.costmodel import HIGH_POWER, evaluate, speedup
from repro.core.coupling import loose_forward, tight_forward
from repro.core.workloads import mlp_workloads

# Regression floor for the staged/fused HBM-byte ratio (BlockSpec-level
# accounting at the canonical 1024x1024 / tile 512 / batch 128 shape).
# Measured 2.21x under kernel v1; kernel v2 (no streamed noise operand,
# fused epilogue) leans the fused side down to 2,629,640 bytes -> 3.49x.
# tests/test_coupling.py guards the same constant so the fused kernel's
# working-set advantage cannot silently erode.
HBM_RATIO_FLOOR = 3.0


def run(verbose: bool = True) -> dict:
    # ---- 1. analytical ------------------------------------------------------
    w = mlp_workloads()
    dig = evaluate(w["dig_1c"], HIGH_POWER)
    tight = evaluate(w["ana_case1"], HIGH_POWER)
    loose = evaluate(w["ana_loose"], HIGH_POWER)
    s_loose, _ = speedup(dig, loose)
    slowdown = loose.time_s / tight.time_s
    if verbose:
        print(table("Tight vs loose coupling — analytical (§VII-B)",
                    ["mapping", "time/inf", "vs digital", "vs tight"],
                    [["digital 1c", fmt_t(dig.time_s), "1.0x", "-"],
                     ["loose (I/O bus)", fmt_t(loose.time_s),
                      f"{s_loose:.1f}x", f"{slowdown:.1f}x slower"],
                     ["tight (ISA ext)", fmt_t(tight.time_s),
                      f"{dig.time_s / tight.time_s:.1f}x", "1.0x"]]))
        print()

    # ---- 2. TPU HBM-traffic accounting (BlockSpec-level) ---------------------
    # numerics of the two paths are identical (tests/test_system.py); the
    # difference is WHERE intermediates live. The fused kernel's traffic
    # follows from its BlockSpecs; the staged path adds a write+read round
    # trip per analog-domain intermediate.
    from repro.core.coupling import hbm_bytes_loose, hbm_bytes_tight
    cfg = AimcConfig(tile_rows=512)
    wmat = jax.random.normal(jax.random.PRNGKey(0), (1024, 1024)) * 0.02
    state = program_linear(wmat, cfg)
    # numerics cross-check on this container
    xv = jax.random.normal(jax.random.PRNGKey(1), (128, 1024))
    dt = float(jnp.max(jnp.abs(tight_forward(state, xv, cfg)
                               - loose_forward(state, xv, cfg))))
    b_tight = hbm_bytes_tight(state, 128)
    b_loose = hbm_bytes_loose(state, 128)
    if verbose:
        print(table("Tight vs loose — HBM bytes per call (TPU adaptation)",
                    ["mapping", "HBM bytes", "ratio", "max |y_t - y_l|"],
                    [["tight (fused kernel)", f"{b_tight:,}", "1.0x",
                      f"{dt:.1e}"],
                     ["loose (HBM-staged)", f"{b_loose:,}",
                      f"{b_loose / b_tight:.2f}x", "-"]]))
        print()

    # ---- 3. measured consistency layer ---------------------------------------
    # wallclock of the two executable paths on this host, plus the backend's
    # own bytes-accessed view of the lowered computations. On CPU the
    # compiler reports identical traffic (no VMEM/HBM split exists), so the
    # BlockSpec accounting above stays the quantitative gap; on TPU the
    # lowered ratio is the measured twin of that accounting.
    meas = {}
    for name, fn in (("tight", tight_forward), ("loose", loose_forward)):
        jitted = jax.jit(lambda v, f=fn: f(state, v, cfg))
        compiled = jitted.lower(xv).compile()
        jax.block_until_ready(jitted(xv))
        t0 = time.perf_counter()
        for _ in range(10):
            y = jitted(xv)
        jax.block_until_ready(y)
        meas[name] = (time.perf_counter() - t0) / 10, \
            compiled.cost_analysis().get("bytes accessed", 0.0)
    t_ratio = meas["loose"][0] / meas["tight"][0]
    bytes_distinct = meas["tight"][1] != meas["loose"][1]
    if verbose:
        rows = [[n, fmt_t(meas[n][0]), f"{meas[n][1]:,.0f}"]
                for n in ("tight", "loose")]
        rows.append(["loose/tight", f"{t_ratio:.2f}x",
                     f"{meas['loose'][1] / max(meas['tight'][1], 1):.2f}x"
                     if bytes_distinct else "1.00x (CPU: no HBM split)"])
        print(table("Tight vs loose — measured (wallclock + lowered bytes)",
                    ["mapping", "wallclock", "bytes accessed"], rows))
        print(f"  predicted loose/tight slowdown (analytical, ARM system): "
              f"{slowdown:.2f}x; BlockSpec HBM ratio: "
              f"{b_loose / b_tight:.2f}x (floor {HBM_RATIO_FLOOR}x)")
        print()
    return {"analytical": (dig, tight, loose),
            "bytes": (b_tight, b_loose),
            "measured": meas, "t_ratio": t_ratio,
            "s_loose": s_loose, "slowdown": slowdown}


def checks(results=None) -> list[Check]:
    results = results or run(verbose=False)
    b_tight, b_loose = results["bytes"]
    return [
        Check("loose speedup over digital (paper: 4.1x)",
              results["s_loose"], 4.1),
        Check("loose slowdown vs tight (paper: up to 3.1x)",
              results["slowdown"], 3.1, rtol=0.2),
        Check("staged(loose) HBM bytes vs fused(tight, kernel v2)",
              b_loose / b_tight, 3.49, rtol=0.15),
        Check(f"HBM byte ratio holds the {HBM_RATIO_FLOOR}x recorded floor",
              min(b_loose / b_tight, HBM_RATIO_FLOOR), HBM_RATIO_FLOOR,
              rtol=0),
    ]


if __name__ == "__main__":
    res = run()
    for c in checks(res):
        print(c.row())
