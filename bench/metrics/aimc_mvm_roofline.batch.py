"""The AIMC kernel's share of its roofline: the least time the chip could
take for the window's crossbar calls (each the larger of its int8 ops at
the int8 peak and its bytes at the HBM peak, from logical shapes), over the
kernel's summed device time in the trace. The calls are every decode step
times every mapped matrix of every layer at n_slots rows, and every
prefill's at prompt_pad rows; the reader returns nothing unless the trace
holds exactly that many kernel events."""

from bench import counts
from bench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    c, eng = ctx.window.counters, ctx.engine
    calls = (counts.aimc_calls(ctx.dims, eng["n_slots"], c["n_steps"])
             + counts.aimc_calls(ctx.dims, eng["prompt_pad"],
                                 c["n_prefills"]))
    lo, hi = tr.window(ctx.trace)
    kern = [e for e in ctx.trace.ops if lo <= e[0] < hi and tr.is_kernel(e)]
    if not kern:
        return None
    if len(kern) != len(calls):
        ctx.notes.append(f"aimc_mvm_roofline: {len(kern)} kernel events in "
                         f"the trace against {len(calls)} counted calls; "
                         f"not reported")
        return None
    least, bound = counts.roofline_seconds(calls, ctx.peaks)
    ctx.notes.append(f"aimc_mvm_roofline: {len(calls)} calls, bound by "
                     f"{bound}, least {least:.6f} s")
    return 100.0 * least / (sum(e[1] for e in kern) * 1e-9)
