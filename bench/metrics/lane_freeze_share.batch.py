"""Share of the decode closure's device time (its ``jit_traced`` module
events in the window) in ops under the ``lane_freeze`` scope: the select
that keeps frozen decode lanes' cache and token unchanged. The note splits
the module's time by scope, with the ops that carry no tf_op path
(``untagged``) and the module time in which no op ran."""

from bench import program_trace as pt


def read(ctx):
    if ctx.trace is None:
        return None
    total, ops = pt.module_self_times(ctx.trace)
    if not total:
        return None
    secs = pt.scope_seconds(total, ops)
    ctx.notes.append(pt.scope_note(ctx.trace))
    return 100.0 * secs.get("lane_freeze", 0.0) / (total * 1e-9)
