"""The grouped expert kernel's share of its roofline: the least time the
chip could take for the window's grouped calls (each the larger of its int8
ops at the int8 peak and its bytes at the HBM peak, from logical shapes,
`bench.counts_moe`), over the kernel's summed device time in the trace
(its ``aimc_expert_pallas`` events). The calls are two per layer (gate+up,
down) of every decode step at n_slots x top_k routed rows and of every
prefill at prompt_pad x top_k. The bytes count every expert's codes, as a
prefill reads them; a decode step of 32 lanes touches about 98% of the
experts, so this is the least traffic to within about 2%. The reader
returns nothing unless the trace holds exactly that many kernel events."""

from bench import counts
from bench import trace as tr
from bench.counts_moe import expert_calls, moe_of

KERNEL = "aimc_expert_pallas"
CONFIG = "mellum2-12b-aimc"


def read(ctx):
    if ctx.trace is None:
        return None
    c, eng = ctx.window.counters, ctx.engine
    moe = moe_of(CONFIG)
    calls = (expert_calls(ctx.dims, moe, eng["n_slots"], c["n_steps"])
             + expert_calls(ctx.dims, moe, eng["prompt_pad"],
                            c["n_prefills"]))
    lo, hi = tr.window(ctx.trace)
    kern = [e for e in ctx.trace.ops
            if lo <= e[0] < hi and tr.op_label(e).startswith(KERNEL)]
    if not kern:
        return None
    if len(kern) != len(calls):
        ctx.notes.append(f"expert_mvm_roofline: {len(kern)} kernel events "
                         f"in the trace against {len(calls)} counted calls; "
                         f"not reported")
        return None
    least, bound = counts.roofline_seconds(calls, ctx.peaks)
    ctx.notes.append(f"expert_mvm_roofline: {len(calls)} calls, bound by "
                     f"{bound}, least {least:.6f} s")
    return 100.0 * least / (sum(e[1] for e in kern) * 1e-9)
