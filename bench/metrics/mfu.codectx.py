"""Whole-step share of the chip's peak in a sparse-expert cell: the active
work of every request of the window (int8 crossbar ops -- the attention
projections and each token's top_k routed experts -- at the int8 peak;
other matmul FLOPs -- the router, attention over each layer's real context,
window-limited on sliding layers, and the float32 head -- at the bf16
peak), as time at peak, over the window's wall time. Counts from logical
shapes (`bench.counts_moe.request_work`); `bench.counts.request_work`
counts one dense FFN per token and would be wrong here."""

from bench.counts_moe import moe_of, request_work

CONFIG = "mellum2-12b-aimc"


def read(ctx):
    served = ctx.window.served
    if not served or not ctx.window.wall_s:
        return None
    moe = moe_of(CONFIG)
    ops = flops = 0
    for prompt, toks, _ in served.values():
        if toks:
            o, f = request_work(ctx.dims, moe, len(prompt), len(toks))
            ops, flops = ops + o, flops + f
    t_peak = ops / ctx.peaks["int8_ops"] + flops / ctx.peaks["bf16_flops"]
    return 100.0 * t_peak / ctx.window.wall_s
