"""Reduction of a profiler trace (``*.xplane.pb``) to the benchmark's
device numbers.

`read` turns the file into a `Trace`: the device's op and module events and
the harness's own host spans (`jax.profiler.TraceAnnotation` names that start
with ``bench.``), each as (start_ns, duration_ns, name). Everything
after that is plain arithmetic on those lists, so the tests check it on a
small recorded trace without a chip.

Names are matched as the chip's trace prints them: an XLA module is named
after the jitted function (``jit__prefill_fn(<hash>)``, ``jit_traced(<hash>)``
for the engine's decode closure); an op event's name is its HLO instruction
(``%fusion.51 = f32[...] fusion(...)``), and the Pallas kernel's custom call
is named after its jitted wrapper (``%aimc_matmul_pallas_v2.44 = ...``).
Control-flow ops (``while``) span the ops of their bodies, so op time is
counted as self time.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL = "aimc_matmul_pallas"
_HLO_NAME = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")


@dataclasses.dataclass
class Trace:
    ops: list            # (start_ns, dur_ns, name) on the device
    modules: list        # (start_ns, dur_ns, name) on the device
    spans: list          # (start_ns, dur_ns, name) harness host spans
    n_devices: int = 1

    def span(self, name: str):
        """(start_ns, end_ns) covering every host span named ``name``."""
        hits = [(s, s + d) for s, d, n in self.spans if n == name]
        if not hits:
            return None
        return min(a for a, _ in hits), max(b for _, b in hits)


def profiler_options():
    """Device and host ops only: the Python tracer would record every
    interpreter call of the window."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def read(trace_dir: str, device_prefix: str = "/device:TPU:") -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    ops, modules, spans, devices = [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            devices += 1
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((float(ev.start_ns), float(ev.duration_ns),
                                 ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((float(ev.start_ns),
                                      float(ev.duration_ns), ev.name))
    return Trace(ops=ops, modules=modules, spans=spans,
                 n_devices=max(devices, 1))


def clip(events, lo: float, hi: float) -> list:
    """Events overlapping [lo, hi), cut to it."""
    out = []
    for s, d, n in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b - a, n))
    return out


def union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for s, d, _ in sorted(events):
        e = s + d
        if s >= end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """(start_ns, length_ns) of every stretch of [lo, hi) with no event."""
    out, cur = [], lo
    for s, d, _ in sorted(events):
        if s > cur:
            out.append((cur, s - cur))
        cur = max(cur, s + d)
    if hi > cur:
        out.append((cur, hi - cur))
    return out


def window(tr: Trace) -> tuple[float, float]:
    """The measured window: the harness's ``bench.window`` span, or, where
    it is missing, the extent of the device ops."""
    w = tr.span("bench.window")
    if w is not None:
        return w
    return (min(s for s, _, _ in tr.ops),
            max(s + d for s, d, _ in tr.ops))


def busy_and_window_s(tr: Trace) -> tuple[float, float]:
    """(seconds some op ran on a device, averaged over devices; window s)."""
    lo, hi = window(tr)
    busy = union_ns(clip(tr.ops, lo, hi))
    return busy / tr.n_devices * 1e-9, (hi - lo) * 1e-9


def op_label(event) -> str:
    """An op's instruction name without its numeric suffix
    (``%fusion.51 = ...`` -> ``fusion``)."""
    m = _HLO_NAME.match(event[2])
    return m.group(1) if m else event[2].split("(")[0][:64]


def is_kernel(event) -> bool:
    return op_label(event).startswith(KERNEL)


def self_times(events) -> list:
    """(event, self ns): each event's duration less the part of it that the
    events nested inside it cover."""
    out, stack = [], []            # stack of [end, index into out]
    for ev in sorted(events, key=lambda e: (e[0], -e[1])):
        s, d = ev[0], ev[1]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:                  # nested in the innermost open event
            out[stack[-1][1]][1] -= min(d, stack[-1][0] - s)
        out.append([ev, d])
        stack.append([s + d, len(out) - 1])
    return out


def top_ops(tr: Trace, k: int = 10) -> list:
    """[label, seconds] of the ``k`` op labels with the most device self
    time in the window."""
    lo, hi = window(tr)
    tot = {}
    for ev, ns in self_times(clip(tr.ops, lo, hi)):
        lab = op_label(ev)
        tot[lab] = tot.get(lab, 0.0) + ns
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[lab, ns * 1e-9] for lab, ns in ranked]


def idle_gaps(tr: Trace, k: int = 10) -> list:
    """[label, seconds] of the ``k`` longest idle stretches of the device in
    the window, each labelled by the innermost harness span it falls in."""
    lo, hi = window(tr)
    host = [s for s in tr.spans if s[2] != "bench.window"]
    out = []
    for start, length in sorted(gaps(clip(tr.ops, lo, hi), lo, hi),
                                key=lambda g: -g[1])[:k]:
        mid = start + length / 2
        inside = [s for s in host if s[0] <= mid < s[0] + s[1]]
        label = min(inside, key=lambda s: s[1])[2] if inside else "outside"
        out.append([label, length * 1e-9])
    return out
