"""The traffic generator: deterministic by seed, the same work for every
seed, and the copied percentile arithmetic."""

import math

import pytest

from bench import traffic
from bench.system import load_traffic

VOCAB = 1000


@pytest.mark.parametrize("mix", ["batch", "online"])
def test_same_seed_same_requests(mix):
    m = load_traffic(mix)
    if m["driver"] == "backlog":
        a = traffic.backlog_job(m, 2**33 + 5, 1, VOCAB)
        b = traffic.backlog_job(m, 2**33 + 5, 1, VOCAB)
    else:
        a = traffic.open_loop_trace(m, 2**33 + 5, VOCAB, 40.0)
        b = traffic.open_loop_trace(m, 2**33 + 5, VOCAB, 40.0)
    assert a == b


@pytest.mark.parametrize("mix", ["batch", "online"])
def test_every_seed_gets_the_same_work(mix):
    """Seeds draw the tokens; lengths and gaps stay, in the same order."""
    m = load_traffic(mix)
    runs = []
    for seed in (1, 2, 2**32 + 1):
        if m["driver"] == "backlog":
            reqs = traffic.backlog_job(m, seed, 0, VOCAB)
        else:
            reqs = traffic.open_loop_trace(m, seed, VOCAB, 40.0)
        runs.append(reqs)
    keys = [[(len(r.prompt), r.max_new, r.arrival) for r in rs]
            for rs in runs]
    assert keys[0] == keys[1] == keys[2]
    # seeds past 2**32 are distinct seeds, not aliases of small ones
    assert [r.prompt for r in runs[0]] != [r.prompt for r in runs[2]]
    assert [r.prompt for r in runs[0]] != [r.prompt for r in runs[1]]


def test_jobs_differ_in_order_only():
    m = load_traffic("batch")
    a, b = (traffic.backlog_job(m, 5, j, VOCAB) for j in (0, 1))
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_lengths_follow_the_mix():
    m = load_traffic("batch")
    reqs = traffic.backlog_job(m, 3, 0, VOCAB)
    assert len(reqs) == m["job_requests"]
    lens = sorted(len(r.prompt) for r in reqs)
    assert lens[0] >= m["prompt_len"]["min"]
    assert lens[-1] <= m["prompt_len"]["max"]
    assert traffic.percentile(lens, 50) == pytest.approx(
        m["prompt_len"]["median"], rel=0.05)
    assert all(1 <= t < VOCAB for r in reqs for t in r.prompt)


def test_poisson_rate():
    m = dict(load_traffic("online"), rate=10.0)
    reqs = traffic.open_loop_trace(m, 9, VOCAB, 100.0)
    assert len(reqs) == 1000
    # mid-quantile gaps of Exp(10): their mean is 1/10 to within 1%
    assert reqs[-1].arrival / len(reqs) == pytest.approx(0.1, rel=0.01)


@pytest.mark.parametrize("xs,q,want", [
    ([], 50, math.nan),
    ([3.0], 95, 3.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 95, 4.8),
    ([5.0, 1.0, 4.0, 2.0, 3.0], 0, 1.0),
    ([5.0, 1.0, 4.0, 2.0, 3.0], 100, 5.0),
])
def test_percentile(xs, q, want):
    got = traffic.percentile(xs, q)
    if math.isnan(want):
        assert math.isnan(got)
    else:
        assert got == pytest.approx(want)
