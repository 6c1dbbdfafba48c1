"""Whole-step share of the chip's peak: the work the model needs for every
request of the window (int8 crossbar ops at the int8 peak, other matmul
FLOPs -- attention and the float32 head -- at the bf16 peak), as time at
peak, over the window's wall time. Counts from logical shapes
(`bench.counts.request_work`)."""

from bench.counts import request_work


def read(ctx):
    served = ctx.window.served
    if not served or not ctx.window.wall_s:
        return None
    ops = flops = 0
    for prompt, toks, _ in served.values():
        if toks:
            o, f = request_work(ctx.dims, len(prompt), len(toks))
            ops, flops = ops + o, flops + f
    t_peak = ops / ctx.peaks["int8_ops"] + flops / ctx.peaks["bf16_flops"]
    return 100.0 * t_peak / ctx.window.wall_s
