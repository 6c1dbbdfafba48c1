"""95th percentile, over the window's counted requests, of the wait from
the end of a request's ``engine.admit`` span to the end of its
``engine.first_token`` span: the engine reads a lazily admitted request's
first token at the next chunk sync, and the host sees it only then."""

from bench import program_trace as pt
from bench.traffic import percentile


def read(ctx):
    if ctx.trace is None:
        return None
    waits = pt.first_token_waits_ms(pt.program_of(ctx.trace),
                                    ctx.window.served)
    if not waits:
        return None
    ctx.notes.append(f"first_token_wait: {len(waits)} of "
                     f"{len(ctx.window.served)} counted requests; p50 "
                     f"{percentile(waits, 50):.3f} ms")
    return percentile(waits, 95)
