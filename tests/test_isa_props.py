"""Property-based (hypothesis) tests: CM_* accounting invariants and the
DAC/ADC round-trips the crossbar pipeline relies on.

Deterministic twins of the isa invariants live in `tests/test_isa.py` so
coverage survives without the optional dep.
"""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the optional dep
from hypothesis import given, settings, strategies as st

from repro.core import isa
from repro.core.quant import (QMAX, QMIN, adc_quantize, dequantize, quantize,
                              sym_scale)

dims = st.integers(min_value=1, max_value=8192)
tiles = st.integers(min_value=1, max_value=4096)
counts = st.builds(
    isa.CmCounts,
    queue=st.integers(0, 10**6), process=st.integers(0, 10**4),
    dequeue=st.integers(0, 10**6), initialize=st.integers(0, 10**8),
    queue_bytes=st.integers(0, 10**7), dequeue_bytes=st.integers(0, 10**7))


# ---------------------------------------------------------------------------
# CmCounts algebra
# ---------------------------------------------------------------------------

@given(counts, counts, st.integers(0, 1000))
@settings(max_examples=100, deadline=None)
def test_add_scaled_consistency(a, b, m):
    """scaled is repeated addition; addition is commutative; scaling
    distributes — the ledger algebra the schedule/benchmarks rely on."""
    assert a + b == b + a
    assert (a + b).scaled(m) == a.scaled(m) + b.scaled(m)
    total = isa.CmCounts()
    for _ in range(min(m, 7)):
        total = total + a
    assert total == a.scaled(min(m, 7))


@given(st.lists(counts, max_size=12))
@settings(max_examples=50, deadline=None)
def test_total_is_left_fold(cs):
    tot = isa.total(cs)
    ref = isa.CmCounts()
    for c in cs:
        ref = ref + c
    assert tot == ref


# ---------------------------------------------------------------------------
# mvm_counts invariants
# ---------------------------------------------------------------------------

@given(dims, dims, dims, tiles)
@settings(max_examples=200, deadline=None)
def test_mvm_counts_monotone(k, k2, n, tile_rows):
    """Instruction counts are monotone in both matrix dimensions."""
    lo, hi = sorted((k, k2))
    a, b = isa.mvm_counts(lo, n, tile_rows), isa.mvm_counts(hi, n, tile_rows)
    assert a.queue <= b.queue
    assert a.process <= b.process
    assert a.dequeue <= b.dequeue
    assert a.queue_bytes <= b.queue_bytes


@given(dims, dims, tiles)
@settings(max_examples=200, deadline=None)
def test_row_block_structure(k, n, tile_rows):
    """process is exactly the row-block count; dequeue scales with it."""
    c = isa.mvm_counts(k, n, tile_rows)
    rb = -(-k // tile_rows)
    assert c.process == rb
    assert c.dequeue == -(-n // 4) * rb
    assert c.queue == -(-k // 4)
    assert c.queue_bytes == k and c.dequeue_bytes == n * rb
    if tile_rows >= k:
        assert c.process == 1


# ---------------------------------------------------------------------------
# quant round-trips (the fixed-point core of CM_QUEUE / CM_DEQUEUE)
# ---------------------------------------------------------------------------

floats = st.floats(min_value=-1e4, max_value=1e4, allow_nan=False,
                   allow_infinity=False, width=32)


@given(st.lists(st.integers(QMIN, QMAX), min_size=1, max_size=64),
       st.floats(min_value=float(np.float32(1e-4)), max_value=1e3,
                 allow_nan=False, width=32))
@settings(max_examples=100, deadline=None)
def test_int8_codes_roundtrip_exactly(codes, scale):
    """quantize(dequantize(q)) == q: programmed codes survive a digital
    round-trip bit for bit (weights-stationary determinism)."""
    q = jnp.asarray(codes, jnp.int8)
    s = jnp.float32(scale)
    np.testing.assert_array_equal(
        np.asarray(quantize(dequantize(q, s), s)), np.asarray(q))


@given(st.lists(st.integers(QMIN, QMAX), min_size=1, max_size=64),
       st.floats(min_value=0.5, max_value=1e5, allow_nan=False, width=32))
@settings(max_examples=100, deadline=None)
def test_adc_codes_roundtrip_exactly(codes, step):
    """adc_quantize(c * step, step) == c for in-range codes."""
    c = jnp.asarray(codes, jnp.float32)
    got = adc_quantize(c * jnp.float32(step), jnp.float32(step))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(c, dtype=np.int32))


@given(st.lists(floats, min_size=1, max_size=64))
@settings(max_examples=100, deadline=None)
def test_dac_roundtrip_error_within_half_lsb(vals):
    x = jnp.asarray(vals, jnp.float32)
    s = sym_scale(x)
    err = jnp.abs(x - dequantize(quantize(x, s), s))
    assert float(err.max()) <= float(s) * 0.5 + 1e-6
