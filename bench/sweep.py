#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate whose queue
does not grow over any of repeated runs.

    python bench/sweep.py --workload glm4-9b-aimc.online --seconds 15 \
        --rates 6 8 10 12 --repeats 3

One set-up from ``--seed``, then ``--repeats`` open-loop windows per rate
(token ids from seed + repeat) with the cell's traffic at that rate. Per
window it prints one JSON line: the queue wait's median over the first and
the last third of the window's arrivals (a queue that grows shows as a last
third far above the first), the completion rate, and TTFT/TPOT percentiles.
The knee is the highest rate that held in every window; the cell's traffic
file then takes 0.8 x the knee as a number. Needs the TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
from contextlib import nullcontext
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--repeats", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    from bench import system
    from bench.drivers import open_loop
    from bench.run import cell_of, load_benchmark
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = cell_of(load_benchmark(), args.workload)
    conf = system.load_config(cell["config"])
    mix = system.load_traffic(cell["traffic"])
    sut = system.build(conf, args.seed)
    for rate in args.rates:
        for rep in range(args.repeats):
            window(open_loop.run(sut, dict(mix, rate=rate), args.seed + rep,
                                 args.seconds, lambda name: nullcontext()),
                   rate, rep, args.seconds)
    return 0


def window(w, rate: float, rep: int, seconds: float) -> None:
    """Print one window's readings as a JSON line."""
    from bench.traffic import percentile
    q = w.spans["queue_wait_s"]
    third = max(1, len(q) // 3)
    print(json.dumps({
        "rate": rate, "repeat": rep, "counted": w.counters["counted"],
        "finished": w.counters["finished"], "wall_s": w.wall_s,
        "done_per_s": w.counters["finished"] / seconds,
        "queue_wait_p50_first_ms": percentile(q[:third], 50) * 1e3,
        "queue_wait_p50_last_ms": percentile(q[-third:], 50) * 1e3,
        "ttft_p50_ms": percentile(w.spans["ttft_s"], 50) * 1e3,
        "ttft_p95_ms": w.e2e["ttft_p95_ms"],
        "tpot_p95_ms": w.e2e["tpot_p95_ms"],
        "lateness_p95_ms": percentile(w.spans["lateness_s"], 95) * 1e3,
    }), flush=True)


if __name__ == "__main__":
    sys.exit(main())
