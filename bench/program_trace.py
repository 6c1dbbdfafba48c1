#!/usr/bin/env python3
"""The program's own marks in a profiler trace, and the per-layer numbers
read from them.

The serving engine marks its host work with spans named ``engine.*``
(`repro.tracing.span`: admit, prefill, insert, dispatch, sync, first_token,
books), carrying the request (``rid``, ``slot``) or the chunk length
(``n``) as ids, and its model code with `jax.named_scope` names
(`repro.tracing.SCOPES`). The scopes reach the trace as each device op's
``tf_op`` path in the op's event metadata
(``jit(traced)/while/body/closed_call/layers/while/body/block/...``), which
jaxlib's `ProfileData` does not expose: `event_metadata` reads it from the
XSpace bytes with a short protobuf wire-format reader of the fields needed.
The metadata also carries the op's ``program_id``, the number an XLA
module's event is named with (``jit_traced(<program_id>)``), so an op's
path is looked up by its module and its name.

`attach` adds both to a `bench.trace.Trace` read from the same file, as
``trace.program`` (start_ns, dur_ns, name, ids) and ``trace.scopes``
((program_id, op metadata name) -> tf_op path); the readers of the
program's per-layer metrics (``bench/metrics/*``) find nothing to read on a
trace without them.

A temporary bridge: `bench.run` does not call `attach` yet, so run as a
script this module makes one traced run of a cell through
`bench.run.execute` with `bench.trace.read` wrapped to attach both fields,
the program's metrics (`METRICS`) added to the cell's per-layer ones, and
prints the result line:

    python bench/program_trace.py --workload <name> --seed <n> \\
        --seconds <s> [--keep DIR] [--smoke]

Like `bench/run.py` it exits 2 with no result line unless JAX's devices are
TPUs, as many as the cell asks for. ``--keep`` copies the profiler trace
there (gzipped); ``--smoke`` runs the CPU rehearsal's sizes
(`bench.rehearse`) on the chip, which is how the test trace
``bench/tests/data/program.xplane.pb.gz`` was recorded. Once `bench.trace`
calls `attach` and ``BENCHMARK.json`` lists the metrics, `main`,
`_set_up_run`, `newest_xplane` and `METRICS` go, and `attach` and the
arithmetic stay.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import struct
import sys
from pathlib import Path

if __package__ in (None, ""):
    _ROOT = Path(__file__).resolve().parents[1]
    for _p in (str(_ROOT / "src"), str(_ROOT)):
        if _p not in sys.path:
            sys.path.insert(0, _p)

from bench import trace as tr  # noqa: E402
from repro.tracing import SCOPES, SPAN_PREFIX  # noqa: E402

DECODE = "jit_traced"         # the engine's decode closure's XLA module
PREFILL = "jit__prefill_fn"
UNSCOPED = "unscoped"         # a tf_op path with none of the scope names
UNTAGGED = "untagged"         # an op with no tf_op path
BETWEEN = "between ops"       # module time in which no op ran
_MODULE = re.compile(r"^(.+)\((\d+)\)$")
_RESULT_DTYPE = re.compile(r"=\s*\(?\s*([a-z]+\d*)\[")

# the program's metrics: BENCHMARK.json ``per_layer`` entries, as a
# benchmark change would add them
METRICS = [
    {"name": "first_token_wait_p95_ms.online", "unit": "ms",
     "better": "lower", "source": "device_trace",
     "layer": "engine (runtime/engine.py)", "moves": "ttft_p95_ms",
     "workloads": ["glm4-9b-aimc.online"]},
    {"name": "host_gap_per_chunk_ms.online", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "engine (runtime/engine.py)",
     "moves": "tpot_p95_ms", "workloads": ["glm4-9b-aimc.online"]},
    {"name": "lane_freeze_share.batch", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "model step (models/transformer.py)",
     "moves": "out_tok_s", "workloads": ["granite8b-aimc.batch"]},
    {"name": "layer_stack_share.batch", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "model step (models/transformer.py)",
     "moves": "out_tok_s", "workloads": ["granite8b-aimc.batch"]},
]


# ---------------------------------------------------------------------------
# protobuf wire format: just what XSpace's event metadata needs
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int = 0, hi: int | None = None):
    """(field number, value) of each field of the message in buf[lo:hi]:
    an int for varints, (start, end) for length-delimited fields, the raw
    bytes for fixed-width ones."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            v, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"unsupported wire type {wire} at byte {i}")
        yield key >> 3, v


def _int64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span) -> tuple[int, tuple | None]:
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = _int64(v)
        elif f == 2:
            value = v
    return key, value


def _stat(buf, span, stat_names: dict):
    """(name, value) of one XStat; a ref value resolves to the name of the
    stat metadata it points at."""
    mid, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _int64(v)
        elif f == 5:
            value = _text(buf, v)
        elif f == 6:
            value = bytes(buf[v[0]:v[1]])
        elif f == 7:
            value = stat_names.get(v, "")
    return stat_names.get(mid, str(mid)), value


def event_metadata(data: bytes, plane_prefix: str = "") -> dict:
    """{plane name: {metadata id: (name, display_name, {stat: value})}} of
    every plane whose name starts with ``plane_prefix``. Lines and events
    are skipped unread."""
    buf = memoryview(data)
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, metas, stat_metas = "", [], []
        for pf, v in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, v)
            elif pf == 4:
                metas.append(v)
            elif pf == 5:
                stat_metas.append(v)
        if not name.startswith(plane_prefix):
            continue
        stat_names = {}
        for span in stat_metas:
            key, value = _map_entry(buf, span)
            for sf, sv in _fields(buf, *value) if value else ():
                if sf == 2:
                    stat_names[key] = _text(buf, sv)
        events = {}
        for span in metas:
            key, value = _map_entry(buf, span)
            ev_name, display, stats = "", "", {}
            for ef, ev in _fields(buf, *value) if value else ():
                if ef == 2:
                    ev_name = _text(buf, ev)
                elif ef == 4:
                    display = _text(buf, ev)
                elif ef == 5:
                    k, sv = _stat(buf, ev, stat_names)
                    stats[k] = sv
            events[key] = (ev_name, display, stats)
        out[name] = events
    return out


# ---------------------------------------------------------------------------
# reading a trace
# ---------------------------------------------------------------------------

def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_program(path: str) -> tuple:
    """(program spans, scopes) of one ``.xplane.pb`` file: the host events
    named ``engine.*`` as (start_ns, dur_ns, name, {id: int}), and a map
    from each device op's (program_id, metadata name) to its tf_op path."""
    from jax.profiler import ProfileData

    data = Path(path).read_bytes()
    program = []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    ids = {k: int(v) for k, v in ev.stats
                           if isinstance(v, (int, float))}
                    program.append((float(ev.start_ns),
                                    float(ev.duration_ns), ev.name, ids))
    scopes = {}
    for events in event_metadata(data, "/device:").values():
        for name, _, stats in events.values():
            op, pid = stats.get("tf_op"), stats.get("program_id")
            if op and pid is not None:
                scopes[(str(pid), name)] = op
    program.sort()
    return program, scopes


def attach(trace, path: str):
    """``trace`` (a `bench.trace.Trace` of the same file) with the program's
    spans and op scopes attached."""
    trace.program, trace.scopes = read_program(path)
    return trace


def program_of(trace) -> list:
    return getattr(trace, "program", None) or []


def scopes_of(trace) -> dict:
    return getattr(trace, "scopes", None) or {}


# ---------------------------------------------------------------------------
# interval arithmetic on sorted, disjoint (start, end) lists
# ---------------------------------------------------------------------------

def intersect(a, b) -> list:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list:
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if hi > cur:
            out.append((cur, hi))
    return out


def length(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def innermost(spans) -> dict:
    """{name: sorted (start, end) pieces} of the timeline, each piece under
    the innermost of the (properly nested) spans covering it."""
    pieces, stack = {}, []          # stack of [end, name, open-from]

    def close_to(t):
        while stack and stack[-1][0] <= t:
            end, name, start = stack.pop()
            if end > start:
                pieces.setdefault(name, []).append((start, end))
            if stack:
                stack[-1][2] = end

    for s, d, name, *_ in sorted(spans, key=lambda e: (e[0], -e[1])):
        close_to(s)
        if stack and s > stack[-1][2]:
            pieces.setdefault(stack[-1][1], []).append((stack[-1][2], s))
        stack.append([s + d, name, s])
    close_to(float("inf"))
    return {k: sorted(v) for k, v in pieces.items()}


# ---------------------------------------------------------------------------
# the program's per-layer numbers
# ---------------------------------------------------------------------------

def first_token_waits_ms(program, rids) -> list:
    """End of each counted request's ``engine.first_token`` less the end of
    its ``engine.admit``, in ms, for every rid in ``rids`` that has both."""
    admit, first = {}, {}
    for s, d, name, ids in program:
        if "rid" not in ids:
            continue
        if name == "engine.admit":
            admit[ids["rid"]] = s + d
        elif name == "engine.first_token":
            first[ids["rid"]] = s + d
    return [(first[r] - admit[r]) * 1e-6 for r in rids
            if r in admit and r in first]


def host_gaps(trace) -> tuple:
    """(device-idle stretches of the window outside ``bench.sleep``, the
    ``engine.dispatch`` spans starting in the window)."""
    lo, hi = tr.window(trace)
    gaps = [(s, s + n) for s, n in tr.gaps(tr.clip(trace.ops, lo, hi),
                                           lo, hi)]
    sleeps = sorted((s, s + d) for s, d, name in trace.spans
                    if name == "bench.sleep")
    dispatches = [e for e in program_of(trace)
                  if e[2] == "engine.dispatch" and lo <= e[0] < hi]
    return subtract(gaps, sleeps), dispatches


def idle_by_span(trace, idle) -> dict:
    """Seconds of ``idle`` under each innermost ``engine.*`` span; the rest,
    under none, is ``driver``."""
    out = {}
    for name, pieces in innermost(program_of(trace)).items():
        t = length(intersect(idle, pieces))
        if t:
            out[name] = t * 1e-9
    out["driver"] = length(idle) * 1e-9 - sum(out.values())
    return out


def category(op_path: str | None) -> str:
    """The innermost scope name on an op's tf_op path (`UNSCOPED` where it
    has none, `UNTAGGED` where the op has no path)."""
    if op_path is None:
        return UNTAGGED
    for part in reversed(op_path.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def result_dtype(op_name: str) -> str:
    m = _RESULT_DTYPE.search(op_name)
    return m.group(1) if m else "?"


def module_self_times(trace, module: str = DECODE) -> tuple:
    """(ns, ops): the device time of ``module``'s events in the window, and
    (op name, tf_op path or None, self ns) of every op that ran inside them.
    (0, []) on a trace without op scopes."""
    scopes = scopes_of(trace)
    if not scopes:
        return 0.0, []
    lo, hi = tr.window(trace)
    runs = []                      # (start, end, program_id)
    for s, d, name in tr.clip(trace.modules, lo, hi):
        m = _MODULE.match(name)
        if m and m.group(1) == module:
            runs.append((s, s + d, m.group(2)))
    runs.sort()
    starts = [r[0] for r in runs]
    ops = []
    for ev, ns in tr.self_times(tr.clip(trace.ops, lo, hi)):
        mid = ev[0] + ev[1] / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < runs[i][1]:
            ops.append((ev[2], scopes.get((runs[i][2], ev[2])), ns))
    return sum(b - a for a, b, _ in runs), ops


def scope_seconds(total_ns: float, ops) -> dict:
    """{category: seconds} of a module's ops (`module_self_times`), with the
    module time no op covers as `BETWEEN`: the values add up to
    ``total_ns``."""
    out = {}
    for _, path, ns in ops:
        c = category(path)
        out[c] = out.get(c, 0.0) + ns * 1e-9
    out[BETWEEN] = (total_ns - sum(ns for _, _, ns in ops)) * 1e-9
    return out


def scope_note(trace, module: str = DECODE) -> str | None:
    total, ops = module_self_times(trace, module)
    if not total:
        return None
    secs = scope_seconds(total, ops)
    order = tuple(reversed(SCOPES)) + (UNSCOPED, UNTAGGED, BETWEEN)
    parts = [f"{c} {secs[c]:.6f} s ({100 * secs[c] / (total * 1e-9):.2f}%)"
             for c in order if c in secs]
    untagged = {}
    for name, path, ns in ops:
        if path is None:
            lab = tr.op_label((0, 0, name))
            untagged[lab] = untagged.get(lab, 0.0) + ns * 1e-9
    ranked = sorted(untagged.items(), key=lambda kv: -kv[1])[:3]
    return (f"{module} device time by scope, {total * 1e-9:.6f} s: "
            + ", ".join(parts) + "; untagged ops: "
            + (", ".join(f"{k} {v:.6f} s" for k, v in ranked) or "none"))


# ---------------------------------------------------------------------------
# the temporary bridge to a traced run (see the module docstring)
# ---------------------------------------------------------------------------

def log(msg: str) -> None:
    print(f"[program_trace] {msg}", file=sys.stderr, flush=True)


def _set_up_run(keep: str | None):
    """Make `bench.trace.read` attach the program's spans and scopes (and
    keep a gzipped copy of the file under ``keep``)."""
    import gzip
    import shutil

    plain = tr.read

    def read(trace_dir, *args, **kw):
        trace = plain(trace_dir, *args, **kw)
        path = newest_xplane(trace_dir)
        attach(trace, path)
        if keep:
            os.makedirs(keep, exist_ok=True)
            with open(path, "rb") as src, gzip.open(
                    os.path.join(keep, os.path.basename(path) + ".gz"),
                    "wb") as dst:
                shutil.copyfileobj(src, dst)
        for module in (DECODE, PREFILL):
            note = scope_note(trace, module)
            if note:
                log(note)
        return trace

    tr.read = read


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from bench import run
    bench = run.load_benchmark()
    cell = run.cell_of(bench, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX's first device is {devs[0].platform}")
        return 2
    if len(devs) < cell["chips"]:
        log(f"the cell needs {cell['chips']} chips, JAX finds {len(devs)}")
        return 2
    names = {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] += [m for m in METRICS if m["name"] not in names]
    kw = {}
    if args.smoke:
        from bench.rehearse import smoke_kwargs
        kw = smoke_kwargs(args.workload, bench)
    _set_up_run(args.keep)
    result = run.execute(args.workload, args.seed, args.seconds, True,
                         bench=bench, **kw)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
