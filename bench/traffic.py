"""Traffic generation for the benchmark: one general generator, driven by the
parameters of a traffic file (`bench/traffic/<mix>.json`).

Every seed gets the same work. Lengths are the quantiles of the mix's
distribution and Poisson gaps the quantiles of the exponential law, laid out
in one fixed shuffled order (drawn from the job's or trace's own index, not
from the seed); the seed draws the token ids, and the weights. A batch job's
tail and an open loop's bursts are then the same in every run, so the spread
between seeds measures the system, not the draw.

The arithmetic of `percentile` and of exponential arrivals follows
`repro.runtime.batcher` (`percentile`, `poisson_trace`); it is copied here so
that the yardstick does not move with the program.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    """One generated request: ``arrival`` in seconds from the start of
    offered load; ``max_new`` counts every generated token, the first
    (from prefill) included."""
    rid: int
    prompt: tuple[int, ...]
    max_new: int
    arrival: float


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile, q in [0, 100]; nan for no samples."""
    if not xs:
        return float("nan")
    s = sorted(xs)
    if len(s) == 1:
        return float(s[0])
    pos = (len(s) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return float(s[lo] + (s[hi] - s[lo]) * (pos - lo))


def length_quantiles(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of a clipped
    log-normal: ``{"dist": "lognormal", "median", "sigma", "min", "max"}``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = statistics.NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    lo, hi = int(spec["min"]), int(spec["max"])
    return [min(hi, max(lo, int(round(math.exp(mu + sigma * z.inv_cdf(
        (i + 0.5) / n)))))) for i in range(n)]


def exp_gap_quantiles(rate: float, n: int) -> list[float]:
    """``n`` inter-arrival gaps at the mid-quantiles of Exp(rate)."""
    if rate <= 0:
        raise ValueError("rate must be > 0")
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def _rng(seed: int, stream: int) -> np.random.Generator:
    # numpy seeds from any non-negative integer, so seeds past 2**32 differ
    return np.random.default_rng([int(seed), stream])


# the layout's own generator: orders lengths and gaps the same for every seed
LAYOUT = 0


def make_requests(mix: dict, n: int, seed: int, vocab: int,
                  arrivals: Sequence[float] | None = None,
                  rid0: int = 0, stream: int = 0) -> list[Req]:
    """``n`` requests with the mix's length quantiles in the layout's order
    for ``stream``, token ids drawn from ``seed`` uniformly in [1, vocab)."""
    layout = _rng(LAYOUT, stream)
    prompts = length_quantiles(mix["prompt_len"], n)
    outputs = length_quantiles(mix["output_len"], n)
    layout.shuffle(prompts)
    layout.shuffle(outputs)
    rng = _rng(seed, stream)
    arr = list(arrivals) if arrivals is not None else [0.0] * n
    out = []
    for i in range(n):
        toks = rng.integers(1, vocab, size=prompts[i])
        out.append(Req(rid=rid0 + i, prompt=tuple(int(t) for t in toks),
                       max_new=int(outputs[i]), arrival=float(arr[i])))
    return out


def backlog_job(mix: dict, seed: int, job: int, vocab: int) -> list[Req]:
    """Job ``job`` of an offline backlog: ``job_requests`` requests, all due
    at once. Every job holds the same lengths, each job in its own order."""
    n = int(mix["job_requests"])
    return make_requests(mix, n, seed, vocab, rid0=job * n, stream=1 + job)


def open_loop_trace(mix: dict, seed: int, vocab: int,
                    horizon_s: float) -> list[Req]:
    """Poisson arrivals at ``mix["rate"]`` covering ``horizon_s`` seconds:
    ``ceil(rate * horizon_s)`` gaps at the exponential's quantiles in the
    layout's order, the same for every seed."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    rate = float(mix["rate"])
    n = int(math.ceil(rate * horizon_s))
    gaps = exp_gap_quantiles(rate, n)
    _rng(LAYOUT, 0).shuffle(gaps)
    arrivals = np.cumsum(gaps).tolist()
    return make_requests(mix, n, seed, vocab, arrivals=arrivals, stream=1)
