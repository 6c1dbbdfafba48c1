"""Device time of one prefill call ([1, prompt_pad] rows): the mean
duration of the prefill closure's XLA module events in the traced window."""

from bench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = tr.window(ctx.trace)
    mods = [e for e in ctx.trace.modules
            if e[2].startswith("jit__prefill_fn") and lo <= e[0] < hi]
    if not mods:
        return None
    return sum(e[1] for e in mods) / len(mods) * 1e-6
