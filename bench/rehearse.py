#!/usr/bin/env python3
"""Rehearse every cell of ``BENCHMARK.json`` on the CPU at smoke size.

    JAX_PLATFORMS=cpu python bench/rehearse.py [--seconds 2] [--trace]

Each cell runs the whole of `bench.run.execute` -- set-up, driver, metric
readers, the reference check -- with the arch's tiny preset, a small engine
and short traffic. It prints what a run would report, for reading; it never
prints the benchmark's result line, and its numbers are not device numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# engine and traffic at smoke size: lengths fit prompt_pad 32 / max_seq 64
SMOKE_ENGINE = {"n_slots": 4, "prompt_pad": 32, "max_seq": 64}
SMOKE_MIX = {
    "backlog": {"job_requests": 8, "check_requests": 4, "check_prefills": 8,
                "prompt_len": {"dist": "lognormal", "median": 12,
                               "sigma": 0.5, "min": 4, "max": 32},
                "output_len": {"dist": "lognormal", "median": 10,
                               "sigma": 0.5, "min": 4, "max": 32}},
    "open_loop": {"rate": 20.0, "warm_seconds": 0.5, "drain_seconds": 5,
                  "check_requests": 4, "check_prefills": 8,
                  "prompt_len": {"dist": "lognormal", "median": 12,
                                 "sigma": 0.5, "min": 4, "max": 32},
                  "output_len": {"dist": "lognormal", "median": 8,
                                 "sigma": 0.5, "min": 2, "max": 32}},
}
# the v5e table stands in: CPU readings of device metrics mean nothing
CPU_PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12,
             "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def smoke_kwargs(workload: str, bench: dict) -> dict:
    from bench import system
    from bench.run import cell_of
    cell = cell_of(bench, workload)
    driver = system.load_traffic(cell["traffic"])["driver"]
    return {"smoke": True, "engine_over": dict(SMOKE_ENGINE),
            "mix_over": dict(SMOKE_MIX[driver]), "peaks": CPU_PEAKS}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=2**33 + 17)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)

    from bench.run import execute, load_benchmark
    bench = load_benchmark()
    names = args.workload or [w["name"] for w in bench["workloads"]]
    for name in names:
        res = execute(name, args.seed, args.seconds, args.trace, bench=bench,
                      **smoke_kwargs(name, bench))
        print(f"[rehearse] {name} (CPU, smoke size): "
              + json.dumps({k: v for k, v in res.items()
                            if k != "breakdown"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
