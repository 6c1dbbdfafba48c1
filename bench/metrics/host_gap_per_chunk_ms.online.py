"""Device-idle time of the window outside the ``bench.sleep`` stretches of
`bench/drivers/open_loop.py`, over the decode chunks dispatched in the
window (the ``engine.dispatch`` spans). The note splits that idle time by
the innermost ``engine.*`` span open at the time (``driver`` where none
is)."""

from bench import program_trace as pt


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    idle, dispatches = pt.host_gaps(ctx.trace)
    if not dispatches:
        return None
    split = pt.idle_by_span(ctx.trace, idle)
    ctx.notes.append(
        f"host_gap: {pt.length(idle) * 1e-9:.6f} s idle outside bench.sleep "
        f"over {len(dispatches)} chunks; by span: "
        + ", ".join(f"{k} {v:.6f} s" for k, v in
                    sorted(split.items(), key=lambda kv: -kv[1])))
    return pt.length(idle) / len(dispatches) * 1e-6
