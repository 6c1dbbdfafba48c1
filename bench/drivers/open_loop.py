"""Open-loop load: requests arrive on the traffic's schedule whatever the
engine does, driven through the session primitives that
`runtime.server.ModelServer` uses (`begin`, `can_admit`, `admit`, `step`,
`finish`) against the real wall clock.

Each request is timed from its due time. A token is stamped when the host
first sees it after an `admit` or `step` returns; the engine reads a prefill's
first token lazily at the next chunk sync, and that wait counts. Requests due
in the first ``warm_seconds`` are served but not counted; the window counts
those due in the next ``seconds``, and load keeps coming until every counted
request has finished.
"""

from __future__ import annotations

import collections
import time

from bench.drivers.common import Window, add_counters
from bench.traffic import open_loop_trace, percentile


def run(system, mix: dict, seed: int, seconds: float, span) -> Window:
    from repro.runtime.batcher import Request

    eng = system.engine
    warm = float(mix["warm_seconds"])
    horizon = warm + seconds + float(mix["drain_seconds"])
    trace = open_loop_trace(mix, seed, system.cfg.vocab, horizon)
    by_rid = {r.rid: r for r in trace}
    counted = {r.rid for r in trace if warm <= r.arrival < warm + seconds}
    open_counted = set(counted)

    arrivals = collections.deque(trace)
    waiting = collections.deque()
    live = {}                       # rid -> record, admitted and not retired
    noticed, admit_t, first_t, last_t = {}, {}, {}, {}

    def stamp(now):
        for rid, rec in list(live.items()):
            if rec.tokens and rid not in first_t:
                first_t[rid] = now
            if rec.finish_reason:
                last_t[rid] = now
                del live[rid]
                open_counted.discard(rid)

    sess = eng.begin()
    t0 = time.perf_counter()
    with span("bench.window"):
        while open_counted:
            now = time.perf_counter() - t0
            while arrivals and arrivals[0].arrival <= now:
                r = arrivals.popleft()
                noticed[r.rid] = now
                waiting.append(r)
            while (waiting and sess.slots.n_free
                   and eng.can_admit(sess, _req(Request, waiting[0]))):
                r = waiting.popleft()
                t = time.perf_counter() - t0
                admit_t[r.rid] = t
                with span("bench.admit"):
                    eng.admit(sess, _req(Request, r), t)
                live[r.rid] = sess.report.records[r.rid]
                stamp(time.perf_counter() - t0)
            if sess.slots.n_busy:
                with span("bench.step"):
                    eng.step(sess, time.perf_counter() - t0)
                stamp(time.perf_counter() - t0)
            elif arrivals and not waiting:
                with span("bench.sleep"):
                    time.sleep(max(0.0, arrivals[0].arrival
                                   - (time.perf_counter() - t0)))
            elif not arrivals and not waiting:
                break       # nothing left to serve (cannot happen normally)
        eng.cancel_active(sess, time.perf_counter() - t0)
        report = eng.finish(sess, time.perf_counter() - t0)
    wall = time.perf_counter() - t0

    counters = {}
    add_counters(counters, report)
    served, ttft, tpot, qwait, late = {}, [], [], [], []
    for rid in sorted(counted):
        r = by_rid[rid]
        toks = list(report.records[rid].tokens) if rid in report.records \
            else []
        served[rid] = (r.prompt, toks, r.max_new)
        if rid not in last_t:
            continue        # never finished: the check reads it as short
        ttft.append(first_t[rid] - r.arrival)
        qwait.append(admit_t[rid] - r.arrival)
        late.append(noticed[rid] - r.arrival)
        if len(toks) >= 2:
            tpot.append((last_t[rid] - first_t[rid]) / (len(toks) - 1))
    done = len(ttft)
    offered = sum(1 for r in trace if r.arrival <= wall)
    notes = [
        f"open loop at {mix['rate']} req/s: {len(counted)} requests due in "
        f"the window, {done} finished; {offered} offered in {wall:.3f} s",
        f"generator lateness (due -> noticed by the loop) p50/p95/max "
        f"{percentile(late, 50) * 1e3:.3f}/{percentile(late, 95) * 1e3:.3f}/"
        f"{max(late, default=float('nan')) * 1e3:.3f} ms",
        f"ttft p50/p95 {percentile(ttft, 50) * 1e3:.3f}/"
        f"{percentile(ttft, 95) * 1e3:.3f} ms; tpot p50/p95 "
        f"{percentile(tpot, 50) * 1e3:.3f}/{percentile(tpot, 95) * 1e3:.3f} "
        f"ms; queue wait p95 {percentile(qwait, 95) * 1e3:.3f} ms",
    ]
    return Window(
        served=served,
        e2e={"ttft_p95_ms": percentile(ttft, 95) * 1e3,
             "tpot_p95_ms": percentile(tpot, 95) * 1e3},
        counters=counters | {"counted": len(counted), "finished": done,
                             "tokens": sum(len(v[1]) for v in served.values())},
        spans={"queue_wait_s": qwait, "ttft_s": ttft, "tpot_s": tpot,
               "lateness_s": late},
        notes=notes,
        wall_s=wall)


def _req(Request, r):
    return Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                   arrival=r.arrival)
