"""Pallas kernel microbenchmarks (interpret mode on this CPU container).

Wall-clock numbers here are *interpreter* times — meaningless as TPU
performance, reported only to show the harness. The meaningful outputs:

  (a) kernel-vs-oracle agreement across a shape sweep, noise ON (the
      in-kernel counter PRNG must match the oracle's bulk draw);
  (b) the kernel-v2 HBM-traffic ledger per call vs kernel v1 for the
      paper's MLP/LSTM/CNN layer shapes: the `[KB, B, Np]` noise operand is
      GONE (a 4-byte scalar seed replaces it) and the epilogue's separate
      bias/activation op round-trip is fused away;
  (c) the VMEM working-set accounting of the chosen BlockSpecs (no noise
      block under v2), checked against the 16 MB budget;
  (d) fused-epilogue and gate-fused-stack exactness checks.

`run()` returns a JSON-serializable dict — `python -m benchmarks.run --json
BENCH_kernels.json` persists it for cross-PR perf tracking, and `ci.sh
--fast` replays it as a perf-smoke gate.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import Check, table
from repro.core.aimc import AimcConfig, program_linear
from repro.core.coupling import (hbm_bytes_tight, hbm_epilogue_bytes,
                                 hbm_noise_bytes)
from repro.core.noise import NoiseModel, read_sigma_lsb
from repro.core.quant import sym_scale
from repro.kernels import ops, ref

SHAPES = [  # (B, K, N) — kernel-vs-oracle parity sweep
    (8, 256, 256),
    (64, 1024, 1024),
    (128, 512, 2048),
    (16, 300, 200),      # ragged -> padding path
]

# The paper's exploration-layer shapes (MLP Fig. 6, LSTM n_h=750 Table II,
# CNN-F conv2 im2col) at single-inference and batched serving sizes.
PAPER_SHAPES = [  # (name, B, K, N)
    ("mlp_fc 1024x1024 b=1", 1, 1024, 1024),
    ("mlp_fc 1024x1024 b=128", 128, 1024, 1024),
    ("lstm_cell n_h=750 b=1", 1, 800, 3000),
    ("cnn_conv2 5x5x64->256", 64, 1600, 256),
]

NOISY = NoiseModel(sigma_read=0.005)


def vmem_bytes(bb: int, m: int, bn: int) -> int:
    """Per-grid-step VMEM working set of the v2 kernel (no noise block —
    noise is generated in registers/VMEM from the prefetched seed)."""
    return (bb * m * 4          # x block f32
            + m * bn * 1        # stationary int8 weight panel
            + bb * bn * 4       # output block f32
            + bn * 4 + 4        # s_w row + s_x scalar
            + 4)                # prefetched seed


def _traffic_row(state, b: int):
    """Per-call HBM bytes under the v1 contract (streamed noise + separate
    epilogue op) vs kernel v2 (scalar seed + fused epilogue)."""
    v1 = hbm_bytes_tight(state, b, noise_streamed=True, epilogue_fused=False)
    v2 = hbm_bytes_tight(state, b, noise_streamed=False, epilogue_fused=True)
    return {
        "v1_bytes": int(v1),
        "v2_bytes": int(v2),
        "noise_bytes_v1": int(hbm_noise_bytes(state, b, noise_streamed=True)),
        "noise_operand_bytes_v2": 0,    # no [KB, B, Np] operand exists
        "seed_bytes_v2": int(hbm_noise_bytes(state, b, noise_streamed=False)),
        "epilogue_bytes_v1": int(hbm_epilogue_bytes(state, b,
                                                    epilogue_fused=False)),
        "epilogue_bytes_v2": 0,
        "ratio": float(v1 / v2),
    }


def jaxpr_materializes_shape(jaxpr, shape) -> bool:
    """True if any value of `shape` flows anywhere in the computation —
    recursing into nested jaxprs (pjit/scan/pallas bodies), so a noise
    tensor rematerialized INSIDE the jitted kernel wrapper is still seen."""
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            if getattr(getattr(v, "aval", None), "shape", None) == shape:
                return True
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", None)   # ClosedJaxpr -> Jaxpr
            if inner is None and hasattr(param, "eqns"):
                inner = param                        # raw Jaxpr
            if inner is not None and jaxpr_materializes_shape(inner, shape):
                return True
    return False


def _noise_operand_absent(state, xf, s_x, cfg, sigma) -> bool:
    """Structural check: no [KB, B, Np]-shaped value exists anywhere in the
    lowered v2 computation even with noise enabled."""
    kb, m, np_ = state.w_q.shape
    shape = (kb, xf.shape[0], np_)
    jaxpr = jax.make_jaxpr(
        lambda xv, seed: ops.aimc_matmul_v2(
            xv, state.w_q, state.s_w, s_x, seed, adc_step=cfg.adc_step,
            sigma=sigma, impl="pallas_interpret"))(xf, jnp.uint32(1))
    return not jaxpr_materializes_shape(jaxpr.jaxpr, shape)


def run(verbose: bool = True) -> dict:
    cfg = AimcConfig(tile_rows=256, noise=NOISY)
    sigma = read_sigma_lsb(cfg.tile_rows, NOISY)
    seed = jnp.uint32(0xA11CE)

    # ---- (a) kernel vs oracle, in-kernel noise ON ---------------------------
    rows, max_err, cases = [], 0.0, []
    for (b, k, n) in SHAPES:
        kx, kw = jax.random.split(jax.random.PRNGKey(b + k + n))
        x = jax.random.normal(kx, (b, k), jnp.float32)
        w = jax.random.normal(kw, (k, n), jnp.float32) * 0.05
        st = program_linear(w, cfg)
        kb, m, np_ = st.w_q.shape
        xf = jnp.pad(x, ((0, 0), (0, kb * m - k)))
        s_x = sym_scale(xf).reshape(1, 1)

        y_ref = ref.aimc_matmul_ref_v2(xf, st.w_q, st.s_w, s_x, seed,
                                       adc_step=cfg.adc_step, sigma=sigma)
        t0 = time.perf_counter()
        y_pal = ops.aimc_matmul_v2(xf, st.w_q, st.s_w, s_x, seed,
                                   adc_step=cfg.adc_step, sigma=sigma,
                                   impl="pallas_interpret")
        jax.block_until_ready(y_pal)
        t1 = time.perf_counter()
        err = float(jnp.max(jnp.abs(y_ref - y_pal)))
        max_err = max(max_err, err)
        cases.append({"shape": f"{b}x{k}x{n}", "max_err": err,
                      "interpret_wallclock_s": t1 - t0})
        rows.append([f"{b}x{k}x{n}", f"{err:.2e}",
                     f"{(t1 - t0) * 1e3:.0f}ms (interp)"])
    if verbose:
        print(table("AIMC kernel v2 vs oracle (in-kernel noise ON)",
                    ["B x K x N", "max |kernel - oracle|", "interpret time"],
                    rows))
        print()

    # ---- (b) HBM bytes per call: v1 vs v2, paper layer shapes ---------------
    traffic, rows = [], []
    for name, b, k, n in PAPER_SHAPES:
        st = program_linear(jnp.ones((k, n)) * 0.02, cfg)
        t = {"name": name, "b": b, "k": k, "n": n} | _traffic_row(st, b)
        traffic.append(t)
        rows.append([name, f"{t['v1_bytes']:,}", f"{t['v2_bytes']:,}",
                     f"{t['noise_bytes_v1']:,}",
                     t["noise_operand_bytes_v2"],
                     f"{t['epilogue_bytes_v1']:,}", f"{t['ratio']:.2f}x"])
    if verbose:
        print(table(
            "HBM bytes per call: v1 (streamed noise + separate epilogue) "
            "vs kernel v2",
            ["layer", "v1 total", "v2 total", "v1 noise", "v2 noise operand",
             "v1 epilogue", "v1/v2"], rows))
        print()

    # ---- (c/d) exactness + structural checks --------------------------------
    st = program_linear(
        jax.random.normal(jax.random.PRNGKey(3), (512, 384)) * 0.05, cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 512))
    kb, m, np_ = st.w_q.shape
    s_x = sym_scale(x).reshape(1, 1)
    noise_gone = _noise_operand_absent(st, x, s_x, cfg, sigma)

    bias = jax.random.normal(jax.random.PRNGKey(5), (np_,))
    y_fused = ops.aimc_matmul_v2(x, st.w_q, st.s_w, s_x, None, bias,
                                 adc_step=cfg.adc_step, activation="relu",
                                 impl="pallas_interpret")
    y_unf = ops.aimc_matmul_v2(x, st.w_q, st.s_w, s_x,
                               adc_step=cfg.adc_step, impl="pallas_interpret")
    epilogue_exact = bool(jnp.all(
        y_fused == jnp.maximum(y_unf + bias[None, :], 0.0)))

    from repro.kernels import cprng
    w_q = jnp.stack([st.w_q] * 4)
    s_w = jnp.stack([st.s_w] * 4)
    y_stk = ops.aimc_matmul_stacked(x, w_q, s_w, s_x, seed,
                                    adc_step=cfg.adc_step, sigma=sigma,
                                    impl="pallas_interpret")
    stack_exact = all(bool(jnp.all(
        y_stk[g] == ops.aimc_matmul_v2(x, st.w_q, st.s_w, s_x,
                                       cprng.stack_seed(seed, g),
                                       adc_step=cfg.adc_step, sigma=sigma,
                                       impl="pallas_interpret")))
        for g in range(4))

    vm = vmem_bytes(128, 512, 512)
    if verbose:
        print(f"  noise [KB,B,Np] operand absent under v2 (noise on): "
              f"{noise_gone}")
        print(f"  fused epilogue == separate bias/relu ops: {epilogue_exact}")
        print(f"  gate-fused stack == per-gate calls (noise on): "
              f"{stack_exact}")
        print(f"  default BlockSpec VMEM working set: {vm / 2**20:.2f} MiB "
              f"(budget 16 MiB)")
        print()
    return {"max_err": max_err, "vmem": vm, "cases": cases,
            "hbm_traffic": traffic, "noise_operand_gone": noise_gone,
            "epilogue_exact": epilogue_exact, "stack_exact": stack_exact}


def checks(results=None) -> list[Check]:
    results = results or run(verbose=False)
    min_ratio = min(t["ratio"] for t in results["hbm_traffic"])
    worst_noise = max(t["noise_operand_bytes_v2"]
                      for t in results["hbm_traffic"])
    return [
        Check("kernel-oracle max abs err < 1e-5 (noise on)",
              1.0 if results["max_err"] < 1e-5 else 0.0, 1.0, rtol=0.01),
        Check("VMEM working set under 16 MiB",
              1.0 if results["vmem"] < 16 * 2**20 else 0.0, 1.0, rtol=0.01),
        Check("v2 noise-path HBM input bytes == 0 (no [KB,B,Np] operand)",
              1.0 if (worst_noise == 0 and results["noise_operand_gone"])
              else 0.0, 1.0, rtol=0.01),
        Check("fused epilogue == separate bias/activation ops",
              1.0 if results["epilogue_exact"] else 0.0, 1.0, rtol=0.01),
        Check("gate-fused stack == per-gate calls (noise on)",
              1.0 if results["stack_exact"] else 0.0, 1.0, rtol=0.01),
        Check("v1/v2 HBM bytes ratio > 1 on every paper layer",
              1.0 if min_ratio > 1.0 else 0.0, 1.0, rtol=0.01),
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
        payload = {"results": res,
                   "checks": [{"name": c.name, "measured": c.measured,
                               "target": c.target, "ok": c.ok} for c in cs]}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")
    sys.exit(0 if all(c.ok for c in cs) else 1)
