"""Device time per decode step: the summed duration of the decode closure's
XLA module events (``jit_traced``, one event per chunk of up to
decode_chunk steps) in the traced window, over the steps those chunks ran
(`ServeReport.n_steps`)."""

from bench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    steps = ctx.window.counters.get("n_steps", 0)
    lo, hi = tr.window(ctx.trace)
    mods = [e for e in ctx.trace.modules
            if e[2].startswith("jit_traced") and lo <= e[0] < hi]
    if not mods or not steps:
        return None
    return sum(e[1] for e in mods) / steps * 1e-6
