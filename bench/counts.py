"""Operations and bytes from logical shapes: the yardstick for the AIMC
kernel's roofline and for the whole step's share of the chip's peak.

Counts use the model's logical K, N and rows, never the kernel's padded
tiles, so a change of padding or blocking leaves them as they are.

Per AIMC call of B rows through a [K, N] matrix on tiles of M rows:
  ops    2 * B * K * N int8 multiply-accumulates (the crossbar MAC);
  bytes  K * N int8 codes + ceil(K / M) * N float32 column scales
         + B * K float32 inputs + B * N float32 outputs (+ N float32 bias),
         each read or written once: the least HBM traffic of the call.
The arithmetic follows `repro.core.coupling.hbm_bytes_tight` with the
re-streaming terms of one blocking taken out.
"""

from __future__ import annotations

import math


def matrices(dims: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of the projections one layer maps onto the crossbar."""
    d, hq, hkv = dims["d_model"], dims["n_heads"], dims["n_kv_heads"]
    hd, ff = dims["head_dim"], dims["d_ff"]
    return [("wq", d, hq * hd), ("wk", d, hkv * hd), ("wv", d, hkv * hd),
            ("wo", hq * hd, d), ("w_gate", d, ff), ("w_up", d, ff),
            ("w_down", ff, d)]


def aimc_call(b: int, k: int, n: int, tile_rows: int,
              bias: bool = False) -> tuple[int, int]:
    """(int8 ops, HBM bytes) of one AIMC call of ``b`` rows."""
    ops = 2 * b * k * n
    nbytes = (k * n + math.ceil(k / tile_rows) * n * 4 + b * k * 4
              + b * n * 4 + (n * 4 if bias else 0))
    return ops, nbytes


def aimc_calls(dims: dict, rows: int, calls: int) -> list[tuple[int, int]]:
    """(ops, bytes) of each matrix for ``calls`` model calls of ``rows``
    rows, every layer included: one entry per (matrix, layer, call)."""
    out = []
    bias = {"wq", "wk", "wv"} if dims.get("qkv_bias") else set()
    for name, k, n in matrices(dims):
        out += [aimc_call(rows, k, n, dims["tile_rows"], name in bias)] * (
            dims["n_layers"] * calls)
    return out


def roofline_seconds(calls, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take for ``calls`` and which bound holds
    for most of it ("ops" or "bytes")."""
    t_ops = t_bytes = total = 0.0
    for ops, nbytes in calls:
        a, b = ops / peaks["int8_ops"], nbytes / peaks["hbm_bytes_per_s"]
        total += max(a, b)
        t_ops += a if a >= b else 0.0
        t_bytes += b if b > a else 0.0
    return total, ("bytes" if t_bytes >= t_ops else "ops")


def request_work(dims: dict, prompt_len: int, n_tokens: int):
    """(int8 ops, other matmul FLOPs) the model needs to serve one request:
    the prompt's prefill, then ``n_tokens - 1`` decode steps. Attention
    counts the causal context each position really has; the head runs once
    per generated token."""
    d, hq, hd = dims["d_model"], dims["n_heads"], dims["head_dim"]
    layers = dims["n_layers"]
    per_token = 2 * sum(k * n for _, k, n in matrices(dims)) * layers
    head = 2 * d * dims["vocab"]
    p = prompt_len
    ops = per_token * p
    flops = 2 * p * (p + 1) * hq * hd * layers + head      # QK^T and PV
    for i in range(1, n_tokens):
        ctx = p + i
        ops += per_token
        flops += 4 * ctx * hq * hd * layers + head
    return ops, flops
