"""Operations and bytes of a sparse-expert decoder from logical shapes: the
yardstick for the grouped expert kernel's roofline and for the whole
step's share of the chip's peak in the cells of such a model.

``moe`` holds what `bench.check.dims_of` does not pass: ``n_experts``,
``top_k``, ``window`` (the sliding layers' attention window, 0 for none)
and ``layer_types`` (the configuration file's list; layer i is of kind
``layer_types[i % len]``). `moe_of` reads them from a configuration file.

Per grouped expert call over ``rows`` routed rows, G matrices [K, N]
sharing the input (gate and up: G = 2; down: G = 1):
  ops    2 * rows * K * N per matrix;
  bytes  every expert's codes and column scales of each matrix (E * (K * N
         + ceil(K / M) * N * 4)), the float32 rows in (rows * K * 4) and
         out (G * rows * N * 4): each read or written once. The kernel
         fetches only the experts that received rows, but a decode step of
         32 lanes at top-8 over 64 experts touches about 98% of them
         (ServeReport.experts_touched) and a prefill all of them, so this
         is the least traffic to within about 2%.
Per call at ``rows`` tokens the whole step's other shapes follow
`bench.counts`: the attention projections as AIMC calls, attention over
each layer's real context (window-limited on sliding layers), the head.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

from bench import counts

BENCH = Path(__file__).resolve().parent


def moe_of(config: str) -> dict:
    """``moe`` of a configuration file (`bench/configs/<config>.json`)."""
    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    return {"n_experts": conf["num_experts"],
            "top_k": conf["num_experts_per_tok"],
            "window": conf["sliding_window"],
            "layer_types": conf["layer_types"]}


def attention_matrices(dims: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of the attention projections of one layer."""
    return [m for m in counts.matrices(dims) if m[0] in ("wq", "wk", "wv",
                                                          "wo")]


def expert_matrices(dims: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of one expert's projections."""
    return [m for m in counts.matrices(dims) if m[0] in ("w_gate", "w_up",
                                                          "w_down")]


def expert_call(rows: int, mats, n_experts: int,
                tile_rows: int) -> tuple[int, int]:
    """(int8 ops, HBM bytes) of one grouped call over ``rows`` routed rows
    through the matrices ``mats`` [(name, K, N)], which share the input."""
    k = mats[0][1]
    ops = sum(2 * rows * kk * n for _, kk, n in mats)
    nbytes = rows * k * 4
    for _, kk, n in mats:
        nbytes += (n_experts * (kk * n + math.ceil(kk / tile_rows) * n * 4)
                   + rows * n * 4)
    return ops, nbytes


def expert_calls(dims: dict, moe: dict, tokens: int,
                 calls: int) -> list[tuple[int, int]]:
    """(ops, bytes) of the grouped calls of ``calls`` model calls at
    ``tokens`` tokens each, every layer included: per layer one gate+up
    call and one down call, each over tokens * top_k routed rows."""
    gate_up, down = expert_matrices(dims)[:2], expert_matrices(dims)[2:]
    rows = tokens * moe["top_k"]
    per = [expert_call(rows, gate_up, moe["n_experts"], dims["tile_rows"]),
           expert_call(rows, down, moe["n_experts"], dims["tile_rows"])]
    return per * (dims["n_layers"] * calls)


def _window(moe: dict, layer: int) -> int:
    kinds = moe["layer_types"]
    kind = kinds[layer % len(kinds)]
    return moe["window"] if kind == "sliding_attention" else 0


def request_work(dims: dict, moe: dict, prompt_len: int, n_tokens: int):
    """(int8 ops, other matmul FLOPs) the model needs to serve one request:
    the prompt's prefill, then ``n_tokens - 1`` decode steps. Per token and
    layer: the attention projections and the top_k routed experts on the
    crossbar; the float32 router (2 * d * n_experts); attention over the
    context the position really sees (the window's on a sliding layer).
    The head runs once per generated token. With n_experts 0 (no router),
    top_k 1, window 0 this is `bench.counts.request_work` of a dense
    decoder whose FFN is d_ff wide."""
    d, hq, hd = dims["d_model"], dims["n_heads"], dims["head_dim"]
    layers = dims["n_layers"]
    proj = 2 * sum(k * n for _, k, n in attention_matrices(dims))
    expert = 2 * sum(k * n for _, k, n in expert_matrices(dims))
    per_token = (proj + moe["top_k"] * expert) * layers
    router = 2 * d * moe["n_experts"] * layers
    head = 2 * d * dims["vocab"]
    p = prompt_len
    total = p + n_tokens - 1
    ops = per_token * total
    flops = router * total + head * n_tokens
    for layer in range(layers):
        # QK^T and PV over the keys each position sees: the prompt's
        # positions in prefill, then one position per decode step
        flops += 4 * _keys_seen(0, total, _window(moe, layer)) * hq * hd
    return ops, flops


def _keys_seen(lo: int, hi: int, window: int) -> int:
    """Sum over query positions lo..hi-1 of the keys each attends to: pos+1,
    at most ``window`` (0: no window)."""
    def upto(n):          # positions 0..n-1
        if not window or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return upto(hi) - upto(lo)
