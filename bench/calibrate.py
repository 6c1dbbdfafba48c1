#!/usr/bin/env python3
"""Readings for a cell's correctness limits: the program's and each
control's, on many seeds in one process.

    python bench/calibrate.py --workload <name> --seconds 8 --seeds 11 12 13

Each seed runs the cell as `bench/run.py` does (set-up from the seed, a
window at the cell's own load, the reference over a sample of what it
served) and also puts each control of the configuration's
``check.controls`` (the reference one precision below the configured one)
in the program's place over the same sequences and prefills, and judges it
against the same limits. The program's readings set the lower end of a
limit, the controls' the upper end. One JSON line per seed; nothing here is
a benchmark result. Needs the TPU, as a run does.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    from bench import system
    from bench.run import cell_of, execute, load_benchmark
    cell = cell_of(load_benchmark(), args.workload)
    controls = tuple(system.load_config(cell["config"])["check"]["controls"])
    for seed in args.seeds:
        res = execute(args.workload, seed, args.seconds, False,
                      controls=controls)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": res["correct"], "readings": res["readings"],
            "controls": {c: {"correct": v["correct"],
                             "readings": v["readings"]}
                         for c, v in res["controls"].items()},
            "short": res["limits"]["short"]["value"],
            "attempted": res["attempted"], "metrics": res["metrics"],
            "memory_peak_bytes": res["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
