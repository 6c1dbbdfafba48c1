"""Offline backlog: back-to-back jobs, each served by `ServeEngine.serve()`
(the double-buffered front-door loop) with every request due at once.

Jobs start until ``seconds`` have elapsed; every job that starts is served
whole and counted. Time is the host wall clock around each `serve()` call,
which returns only once its last token has been read back."""

from __future__ import annotations

import time

from bench.drivers.common import Window, add_counters
from bench.traffic import backlog_job


def run(system, mix: dict, seed: int, seconds: float, span) -> Window:
    from repro.runtime.batcher import Request

    eng = system.engine
    served, counters = {}, {}
    tokens = 0
    busy_s = 0.0
    job = 0
    t_start = time.perf_counter()
    with span("bench.window"):
        while True:
            reqs = [Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                            arrival=0.0)
                    for r in backlog_job(mix, seed, job, system.cfg.vocab)]
            t0 = time.perf_counter()
            with span("bench.serve"):
                report = eng.serve(reqs)
            dt = time.perf_counter() - t0
            busy_s += dt
            job += 1
            add_counters(counters, report)
            for r in reqs:
                rec = report.records[r.rid]
                served[r.rid] = (r.prompt, list(rec.tokens), r.max_new)
                tokens += len(rec.tokens)
            if time.perf_counter() - t_start >= seconds:
                break
    wall = time.perf_counter() - t_start
    return Window(
        served=served,
        e2e={"out_tok_s": tokens / busy_s},
        counters=counters | {"jobs": job, "tokens": tokens,
                             "serve_s": busy_s},
        notes=[f"{job} jobs of {mix['job_requests']} requests, {tokens} "
               f"tokens in {busy_s:.3f} s of serve() ({wall:.3f} s window)"],
        wall_s=busy_s)
