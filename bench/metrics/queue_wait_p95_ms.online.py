"""95th percentile, over the counted requests, of the wait from a request's
due time to the start of its `admit` call (harness span)."""

from bench.traffic import percentile


def read(ctx):
    waits = ctx.window.spans.get("queue_wait_s")
    if not waits:
        return None
    return percentile(waits, 95) * 1e3
