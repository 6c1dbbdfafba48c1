#!/usr/bin/env python3
"""One cell, one run:

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

The cell's entry in ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``) and traffic (``bench/traffic/<mix>.json``);
the traffic names its driver (``bench/drivers/<driver>.py``), and each
per-layer metric has a reader of its own (``bench/metrics/<metric>.py``).

Set-up (``setup_s``: process start to the end of warm-up) builds the served
AIMC engine from the seed (`bench.system.build`). The window drives it for
``--seconds``; with ``--trace 1`` the profiler records the window and the
run reports the cell's per-layer metrics, otherwise its end-to-end ones.
After the window, the device's peak memory is read, the engine is freed, and
the plain reference checks what the window served (`bench.check`).

Exits nonzero with no result line unless JAX's devices are TPUs, as many as
the cell asks for. The last line of standard output is one JSON object; the
last lines of standard error are the numbers compared, beside their limits.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, system  # noqa: E402
from bench import trace as trace_lib  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_of(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"unknown workload {workload!r}")


def reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", BENCH / "metrics" /
        f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric's reader reads."""
    window: object
    trace: object
    dims: dict
    engine: dict
    peaks: dict
    notes: list


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            bench: dict | None = None, smoke: bool = False,
            engine_over: dict | None = None, mix_over: dict | None = None,
            peaks: dict | None = None, after_build=None,
            controls=()) -> dict:
    """One run of a cell. Returns the result object (without printing).

    ``smoke``, ``engine_over``, ``mix_over`` and ``peaks`` shrink the run
    for rehearsals and tests on the CPU; ``after_build(system)`` lets a test
    break the timed path underneath; ``controls`` (names from the
    configuration's ``check.controls``) adds each control's verdict, with
    the control in the program's place, and the readings, for calibration
    and for the tests of the check."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    bench = bench or load_benchmark()
    cell = cell_of(bench, workload)
    conf = system.load_config(cell["config"])
    mix = dict(system.load_traffic(cell["traffic"]), **(mix_over or {}))
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    sut = system.build(conf, seed, smoke=smoke, engine_over=engine_over)
    setup_s = time.perf_counter() - T_START
    if after_build is not None:
        after_build(sut)
    engine = dict(conf["engine"], **(engine_over or {}))
    dims = check.dims_of(sut.cfg, conf)
    dev = jax.devices()[0]
    log(f"{workload} seed {seed}: set-up {setup_s:.3f} s (weights "
        f"{sut.phases['weights_s']:.3f} s, warm-up "
        f"{sut.phases['warmup_s']:.3f} s, compiled "
        f"{sut.phases['compiled']}); {sut.program.summary()}")

    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    span = jax.profiler.TraceAnnotation if trace else (lambda name:
                                                       nullcontext())
    if trace:
        jax.profiler.start_trace(
            trace_dir, profiler_options=trace_lib.profiler_options())
    try:
        window = driver.run(sut, mix, seed, seconds, span)
    finally:
        if trace:
            jax.profiler.stop_trace()
    for line in window.notes:
        log(line)
    compiled = sut.engine.compile_counts()
    if compiled != sut.phases["compiled"]:
        raise SystemExit(f"the window compiled: {sut.phases['compiled']} "
                         f"after warm-up, {compiled} after the window")

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    peaks = peaks or peaks_for(dev.device_kind)

    out = {"metrics": {}}
    notes = []
    if trace:
        t0 = time.perf_counter()
        tr = trace_lib.read(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy, win = trace_lib.busy_and_window_s(tr)
        device |= {"busy_s": busy, "window_s": win}
        ctx = Ctx(window=window, trace=tr, dims=dims, engine=engine,
                  peaks=peaks, notes=notes)
        for m in bench["per_layer"]:
            if reports(m, workload):
                v = load_reader(m["name"])(ctx)
                if v is not None and math.isfinite(v):
                    out["metrics"][m["name"]] = {"value": v,
                                                 "unit": m["unit"]}
        out["breakdown"] = {"device_ops": trace_lib.top_ops(tr),
                            "idle_gaps": trace_lib.idle_gaps(tr)}
        log(f"trace: {len(tr.ops)} device ops, {len(tr.modules)} modules, "
            f"read in {time.perf_counter() - t0:.3f} s")
    else:
        e2e = dict(window.e2e, setup_s=setup_s)
        for m in bench["end_to_end"]:
            if reports(m, workload):
                out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                             "unit": m["unit"]}
    for line in notes:
        log(line)

    # free the program's state before the reference runs
    del sut, driver
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    t0 = time.perf_counter()
    verdict = check.judge(conf, mix, dims, seed, window.served, engine,
                          controls=controls)
    log(f"check: {len(verdict['sampled']['sequences'])} sequences and "
        f"{len(verdict['sampled']['prefills'])} prefills, "
        f"{verdict['scored_tokens']} served tokens scored against the "
        f"reference in {time.perf_counter() - t0:.3f} s ({live} bytes of "
        f"the program still live); readings "
        + json.dumps(verdict["readings"]))
    short = verdict["limits"]["short"]["value"]
    return {"correct": verdict["correct"], "attempted": len(window.served),
            "failed": short, "metrics": out["metrics"], "device": device,
            **({"breakdown": out["breakdown"]} if trace else {}),
            **({"readings": verdict["readings"],
                "controls": verdict["controls"]} if controls else {}),
            "limits": verdict["limits"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    bench = load_benchmark()
    cell = cell_of(bench, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX's first device is {devs[0].platform}")
        return 2
    if len(devs) < cell["chips"]:
        log(f"the cell needs {cell['chips']} chips, JAX finds {len(devs)}")
        return 2

    result = execute(args.workload, args.seed, args.seconds,
                     bool(args.trace), bench=bench)
    for name, v in result["limits"].items():
        log(f"compared {name}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
