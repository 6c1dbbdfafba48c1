"""Share of the decode closure's device time (its ``jit_traced`` module
events in the window) in ops under the ``layers`` scope and under no
``block``: the layer scan's own slices of its stacked inputs (codes, cache)
and its stacking of the new cache. The note splits that time by the result
dtype in the op's HLO text (``s8`` codes, ``f32`` cache)."""

from bench import program_trace as pt


def read(ctx):
    if ctx.trace is None:
        return None
    total, ops = pt.module_self_times(ctx.trace)
    if not total:
        return None
    by_dtype = {}
    for name, path, ns in ops:
        if pt.category(path) == "layers":
            dt = pt.result_dtype(name)
            by_dtype[dt] = by_dtype.get(dt, 0.0) + ns
    stack = sum(by_dtype.values())
    ctx.notes.append(
        f"layer_stack: {stack * 1e-9:.6f} s of {total * 1e-9:.6f} s; by "
        "result dtype: " + ", ".join(
            f"{k} {v * 1e-9:.6f} s" for k, v in
            sorted(by_dtype.items(), key=lambda kv: -kv[1])))
    return 100.0 * stack / total
