"""The while-aware HLO analyzer (launch/hlostats.py) vs XLA's cost_analysis.

The roofline table depends on this module being right: XLA counts scan
bodies once; hlostats must (a) agree with XLA on scan-free programs and
(b) multiply while bodies by their trip counts.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlostats import analyze_hlo, parse_module, type_bytes


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_scanfree_matches_xla():
    def f(x, w1, w2):
        return jnp.tanh(jnp.maximum(x @ w1, 0) @ w2)

    comp = _compile(f,
                    jax.ShapeDtypeStruct((128, 512), jnp.float32),
                    jax.ShapeDtypeStruct((512, 1024), jnp.float32),
                    jax.ShapeDtypeStruct((1024, 256), jnp.float32))
    xla = comp.cost_analysis()
    mine = analyze_hlo(comp.as_text())
    assert mine["flops"] == pytest.approx(xla["flops"], rel=0.02)
    assert mine["bytes"] == pytest.approx(xla["bytes accessed"], rel=0.10)


def test_scan_trip_count_multiplied():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    comp = _compile(f, jax.ShapeDtypeStruct((256, 256), jnp.float32),
                    jax.ShapeDtypeStruct((256, 256), jnp.float32))
    mine = analyze_hlo(comp.as_text())
    expected = 10 * 2 * 256 ** 3
    assert mine["flops"] == pytest.approx(expected, rel=0.01)
    # XLA undercounts by the trip count — that's the bug we work around
    assert comp.cost_analysis()["flops"] < expected / 5


def test_nested_scans_multiply_through():
    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    comp = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32),
                    jax.ShapeDtypeStruct((128, 128), jnp.float32))
    mine = analyze_hlo(comp.as_text())
    assert mine["flops"] == pytest.approx(12 * 2 * 128 ** 3, rel=0.01)


def test_tuple_types_with_index_comments_parse():
    """Regression: /*index=N*/ comments inside tuple types broke the
    instruction regex and silently dropped every while edge."""
    line = ("  %while.437 = (s32[], f32[16,1,1024]{2,1,0}, "
            "/*index=5*/s32[4]{0}) while(%tuple.497), condition=%c, "
            "body=%b, backend_config={\"known_trip_count\":{\"n\":\"4\"}}")
    comps, _ = parse_module("ENTRY %main (p: s32[]) -> s32[] {\n"
                            + line + "\n}\n")
    instrs = comps["main"]
    assert len(instrs) == 1 and instrs[0].opcode == "while"


def test_type_bytes():
    assert type_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert type_bytes("(s32[], bf16[8,8]{1,0})") == 4 + 128
    assert type_bytes("pred[]") == 1


def test_collectives_counted_with_wire_factors():
    import os
    if jax.device_count() < 2:
        pytest.skip("needs >1 device (run under forced host device count)")


def test_dot_general_batched():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    comp = _compile(f, jax.ShapeDtypeStruct((4, 32, 64), jnp.float32),
                    jax.ShapeDtypeStruct((4, 64, 16), jnp.float32))
    mine = analyze_hlo(comp.as_text())
    assert mine["flops"] == pytest.approx(2 * 4 * 32 * 64 * 16, rel=0.05)
