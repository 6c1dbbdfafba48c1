import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import/init: jax locks the device count on first use.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes and extract the roofline terms.

For each cell this script:
  1. builds the step function (train_step / prefill / serve_step),
  2. ``jax.jit(fn, in_shardings, out_shardings).lower(*input_specs(...))``
     with ShapeDtypeStruct stand-ins — no device allocation,
  3. ``.compile()`` against the 16x16 single-pod mesh and the 2x16x16
     multi-pod mesh (the latter proves the ``pod`` axis shards),
  4. records ``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()``
     (FLOPs / bytes) and the collective-traffic histogram parsed from the
     optimized HLO, into ``experiments/dryrun/<cell>.json``.

Usage:
  python -m repro.launch.dryrun --arch granite_8b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both
  python -m repro.launch.dryrun --arch qwen15_110b --shape decode_32k \
      --exec aimc --variant aimc
"""

import argparse
import json
import time
import traceback

from repro.launch.hlostats import analyze_hlo

# TPU v5e hardware constants for the roofline terms (EXPERIMENTS.md §Roofline)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~per chip, 1 concurrent link)


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             exec_mode: str = "digital", variant: str = "baseline",
             out_dir: str = "experiments/dryrun", save: bool = True) -> dict:
    import jax
    from repro.configs import SHAPES, get_arch
    from repro.core.aimc import AimcConfig
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shardings import to_named
    from repro.launch.steps import make_step
    from repro.models.layers import Execution

    spec = get_arch(arch_id)
    cell = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    if exec_mode == "aimc":
        exe = Execution(mode="aimc", aimc=AimcConfig())
    elif exec_mode == "int8":
        exe = Execution(serve_int8=True)
    else:
        exe = Execution()

    rec = {"arch": spec.arch_id, "shape": shape_name, "kind": cell.kind,
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "devices": n_dev, "exec": exec_mode, "variant": variant}
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            bundle = make_step(spec, cell, mesh, exe)
            jitted = jax.jit(
                bundle.fn,
                in_shardings=to_named(bundle.in_shardings, mesh),
                out_shardings=to_named(bundle.out_shardings, mesh),
                donate_argnums=bundle.donate_argnums)
            lowered = jitted.lower(*bundle.abstract_inputs)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            # while-aware per-device stats: XLA's cost_analysis counts scan
            # bodies ONCE; hlostats multiplies by known_trip_count.
            stats = analyze_hlo(compiled.as_text())

        from repro.launch.modelstats import model_flops
        flops = float(stats["flops"])
        bytes_acc = float(stats["bytes"])
        coll = stats["collectives"]
        mflops_dev = model_flops(spec, cell) / n_dev
        rec |= {
            "ok": True,
            "lower_s": round(t1 - t0, 1),
            "compile_s": round(t2 - t1, 1),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                               + getattr(mem, "temp_size_in_bytes", 0)),
            },
            # while-aware per-device totals (launch/hlostats.py)
            "hlo_flops": flops,
            "hlo_bytes": bytes_acc,
            # raw XLA numbers for cross-reference (scan bodies counted once)
            "xla_flops_raw": float(cost.get("flops", 0.0)),
            "xla_bytes_raw": float(cost.get("bytes accessed", 0.0)),
            "model_flops_per_dev": mflops_dev,
            "useful_ratio": mflops_dev / flops if flops else 0.0,
            "collectives": coll,
            "roofline": {
                "compute_s": flops / PEAK_FLOPS,
                "memory_s": bytes_acc / HBM_BW,
                "collective_s": coll.get("total", 0.0) / ICI_BW,
            },
        }
        r = rec["roofline"]
        r["dominant"] = max(
            ("compute_s", "memory_s", "collective_s"), key=lambda k: r[k])
        r["step_s_bound"] = max(r["compute_s"], r["memory_s"],
                                r["collective_s"])
        r["roofline_fraction"] = (
            (mflops_dev / PEAK_FLOPS) / r["step_s_bound"]
            if r["step_s_bound"] else 0.0)
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec |= {"ok": False, "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}

    if save:
        os.makedirs(out_dir, exist_ok=True)
        fname = (f"{spec.arch_id}.{shape_name}."
                 f"{'multi' if multi_pod else 'single'}.{exec_mode}.{variant}"
                 ".json")
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--exec", dest="exec_mode",
                    choices=["digital", "aimc", "int8"], default="digital")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    from repro.configs import all_cells, cells

    if args.all:
        todo = all_cells()
    elif args.arch and args.shape:
        todo = [(args.arch, args.shape)]
    elif args.arch:
        todo = cells(args.arch)
    else:
        ap.error("--arch/--shape or --all required")

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for arch_id, shape_name in todo:
        for multi in meshes:
            rec = run_cell(arch_id, shape_name, multi, args.exec_mode,
                           args.variant, args.out)
            tag = f"{arch_id}/{shape_name}/{'multi' if multi else 'single'}"
            if rec["ok"]:
                r = rec["roofline"]
                print(f"OK  {tag}: compile={rec['compile_s']}s "
                      f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB "
                      f"flops={rec['hlo_flops']:.3g} "
                      f"coll={rec['collectives'].get('total',0)/2**30:.2f}GiB "
                      f"dominant={r['dominant']}")
            else:
                failures += 1
                print(f"FAIL {tag}: {rec['error']}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
