"""Benchmark harness front door: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure (DESIGN.md §7):

  bench_mlp       Fig. 7 / Fig. 8   (MLP cases, both systems)
  bench_lstm      Fig. 10 / Fig. 11 (LSTM n_h sweep, cases)
  bench_cnn       Fig. 13 / Fig. 14 (CNN-F/M/S, 8-core pipeline)
  bench_pipeline  §VII-IX           (executable multi-core schedules vs the
                                     cost model: measured-vs-predicted)
  bench_coupling  §VII-B            (tight vs loose, analytical + lowered)
  bench_accuracy  §III-C            (AIMC output fidelity vs digital)
  bench_kernels   kernels/          (Pallas v2 vs oracle + HBM/VMEM ledgers)
  bench_serving   runtime/engine    (continuous batching vs static batch:
                                     tok/s + latency percentiles on traces)
  bench_server    runtime/server    (multi-tenant multi-model serving: one
                                     crossbar pool, per-tenant SLOs/quotas)
  bench_placement core/placement    (auto-placement: per-layer sums vs
                                     evaluate/schedule at ratio 1.000,
                                     measured-vs-modeled roofline fit)
  bench_roofline  §Roofline         (dry-run table; run dryrun first)

``--json PATH`` writes machine-readable results — per-case wall-clock,
modeled latency, and check pass/fail — so the perf trajectory is tracked
across PRs (``make bench-json`` -> BENCH_kernels.json). ``--only NAME``
restricts to one module (the CI perf-smoke runs ``--only kernels``).

Exit code 1 if any paper-claim validation fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks import (bench_accuracy, bench_cnn, bench_coupling,
                        bench_kernels, bench_lstm, bench_mlp,
                        bench_pipeline, bench_placement, bench_roofline,
                        bench_server, bench_serving)
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    ("mlp", "MLP (paper Fig. 7/8)", bench_mlp),
    ("lstm", "LSTM (paper Fig. 10/11)", bench_lstm),
    ("cnn", "CNN (paper Fig. 13/14)", bench_cnn),
    ("pipeline", "Multi-core schedules (measured vs predicted)",
     bench_pipeline),
    ("coupling", "Coupling (paper §VII-B)", bench_coupling),
    ("accuracy", "Fidelity (paper §III-C)", bench_accuracy),
    ("kernels", "Pallas kernels", bench_kernels),
    ("serving", "Continuous-batching serving engine (static vs engine)",
     bench_serving),
    ("server", "Multi-tenant model server (tenant quotas over one pool)",
     bench_server),
    ("placement", "Auto-placement (placer sums vs model + roofline fit)",
     bench_placement),
]


def _jsonable(obj):
    """Best-effort JSON view of a module's results dict: numpy scalars ->
    Python, arrays/objects that don't serialize are dropped."""
    if isinstance(obj, dict):
        out = {}
        for k, v in obj.items():
            v = _jsonable(v)
            if v is not None:
                out[str(k)] = v
        return out
    if isinstance(obj, (list, tuple)):
        return [x for x in (_jsonable(v) for v in obj) if x is not None]
    if isinstance(obj, (str, bool, int, float)) or obj is None:
        return obj
    if hasattr(obj, "item"):                 # numpy/jax scalar
        try:
            v = obj.item()
        except (TypeError, ValueError):
            return None
        return v if isinstance(v, (str, bool, int, float)) else None
    return None


def write_report(path: str, report: dict, complete: bool) -> bool:
    """Write the JSON artifact — unless the run is PARTIAL (a sub-bench
    crashed, or ``--only`` restricted the module set) and a COMPLETE
    artifact already exists at ``path``.

    BENCH_all.json is the cross-PR perf-trajectory record: clobbering it
    with a partial run would silently erase the last complete baseline. A
    complete run that merely has failing CHECKS still writes — all its data
    is present and the exit code carries the failure (the documented
    pre-existing CNN top-1 failure must not wedge the artifact). Partial
    runs stamp ``"partial": true`` into the payload, so an existing
    partial artifact never blocks a refresh (artifacts written before this
    stamp existed are presumed complete). Returns whether the file was
    written."""
    report = dict(report, partial=not complete)
    if not complete and os.path.exists(path):
        try:
            with open(path) as f:
                prev_complete = not json.load(f).get("partial", False)
        except (OSError, ValueError):
            prev_complete = False              # unreadable: nothing to protect
        if prev_complete:
            print(f"\nNOT writing {path}: this run is partial (crashed "
                  f"sub-bench or --only) and a complete artifact exists "
                  f"(refusing to overwrite the last complete baseline)")
            return False
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"\nwrote {path}")
    return True


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.run")
    ap.add_argument("--json", metavar="PATH",
                    help="write per-case results + check pass/fail as JSON")
    ap.add_argument("--only", metavar="NAME",
                    choices=[k for k, *_ in MODULES],
                    help="run a single benchmark module")
    ap.add_argument("--mesh", metavar="SPEC", default=None,
                    help="also bench the SHARDED serving engine on this "
                         "mesh (data:D,model:M); forces the host-platform "
                         "device count as needed (must precede first "
                         "backend use)")
    args = ap.parse_args(argv)
    if args.mesh:
        from repro.launch.serve import force_host_device_count
        force_host_device_count(args.mesh)
    enable_compile_cache()

    all_checks = []
    report = {"modules": {}}
    errored = []
    t_start = time.time()
    selected = [m for m in MODULES if args.only in (None, m[0])]
    for key, title, mod in selected:
        print(f"\n{'=' * 72}\n{title}\n{'=' * 72}")
        t0 = time.time()
        try:
            if key == "serving" and args.mesh:
                results = mod.run(verbose=True, mesh_arg=args.mesh)
            else:
                results = mod.run(verbose=True)
            checks = mod.checks(results)
        except Exception as e:             # a crashed sub-bench must not
            elapsed = time.time() - t0     # silently vanish from the report
            print(f"  ERROR: {type(e).__name__}: {e}")
            errored.append(key)
            report["modules"][key] = {"title": title, "elapsed_s": elapsed,
                                      "error": f"{type(e).__name__}: {e}"}
            continue
        all_checks.extend(checks)
        for c in checks:
            print(c.row())
        elapsed = time.time() - t0
        print(f"  ({elapsed:.1f}s)")
        report["modules"][key] = {
            "title": title,
            "elapsed_s": elapsed,
            "results": _jsonable(results),
            "checks": [{"name": c.name, "measured": c.measured,
                        "target": c.target, "rtol": c.rtol, "ok": c.ok}
                       for c in checks],
        }

    if args.only is None:
        print(f"\n{'=' * 72}\nRoofline (dry-run table)\n{'=' * 72}")
        bench_roofline.run(verbose=True)

    n_fail = sum(1 for c in all_checks if not c.ok)
    report["summary"] = {"passed": len(all_checks) - n_fail,
                         "total": len(all_checks),
                         "errored_modules": errored,
                         "elapsed_s": time.time() - t_start}
    if args.json:
        # an --only run is partial by construction: it must not clobber a
        # complete multi-module baseline
        write_report(args.json, report,
                     complete=not errored and args.only is None)

    print(f"\n{'=' * 72}")
    print(f"SUMMARY: {len(all_checks) - n_fail}/{len(all_checks)} paper-claim "
          f"validations passed ({time.time() - t_start:.1f}s)"
          + (f"; {len(errored)} module(s) ERRORED: {', '.join(errored)}"
             if errored else ""))
    if n_fail or errored:
        for c in all_checks:
            if not c.ok:
                print(c.row())
        sys.exit(1)


if __name__ == "__main__":
    main()
