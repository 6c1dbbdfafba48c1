"""The program's marks on the profiler's timeline (`repro.tracing`).

* Model scopes: the lowered decode and prefill closures of the served AIMC
  engine (`bench.system` at smoke size: 2 layers of width 512, programmed
  crossbar states) name every scope in their debug text, and the layer
  scan's slices of its stacked inputs sit under ``layers`` but under no
  ``block``.
* The compiled decode closure's loop writes the stacked K/V cache only
  through row scatters: no select, copy or dynamic-update-slice there
  produces a whole stacked cache.
* Engine spans: a profiler trace of a short `serve()`, and of the session
  primitives (`admit`/`step`), holds ``engine.admit``, ``engine.prefill``,
  ``engine.insert`` and ``engine.first_token`` once per request with its
  ``rid``, and ``engine.dispatch``/``engine.sync``/``engine.books`` once per
  decode chunk.
"""

import glob
import math
import os
import re

import jax
import pytest

from repro import tracing
from repro.runtime.batcher import Request
from repro.runtime.engine import ServeReport

SMOKE_ENGINE = {"n_slots": 4, "prompt_pad": 16, "max_seq": 40,
                "decode_chunk": 4}
_LOC = re.compile(r'^#(loc\d+) = loc\("([^"]*)"', re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(")
_CALLEE = re.compile(r"\b(?:body|condition|calls|to_apply)=%([\w.\-]+)")
_SLICE = re.compile(r"stablehlo\.dynamic_slice %[\w#]+(?:, %[\w#]+)*, "
                    r"sizes = \[1(?:, \d+)*\] : \(tensor<(\d+)x[^)]*\).*"
                    r"loc\(#(loc\d+)\)")


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    """Tests compile in memory: the persistent cache stays off."""
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: None)


@pytest.fixture(scope="module")
def sut():
    from bench import system
    conf = system.load_config("granite8b-aimc")
    return system.build(conf, 2**33 + 5, smoke=True,
                        engine_over=dict(SMOKE_ENGINE))


def lowered(sut, closure: str) -> str:
    eng = sut.engine
    if closure == "decode":
        sess = eng.begin()
        fn = eng._decode_jits[eng._ladder[-1]]
        low = fn.lower(eng.params, sess.cache, sess.tok_buf, sess.state)
    else:
        tokens, valid = eng._pad_prompt([3, 1, 4, 1, 5])
        low = eng._jit_prefill.lower(eng.params, tokens, valid)
    return low.as_text(debug_info=True)


def scope_parts(text: str) -> set:
    return {part for _, name in _LOC.findall(text)
            for part in name.split("/")}


@pytest.mark.parametrize("closure,scopes", [
    ("decode", tracing.SCOPES),
    ("prefill", tuple(s for s in tracing.SCOPES
                      if s != tracing.LANE_FREEZE)),
])
def test_lowered_closures_name_every_scope(sut, closure, scopes):
    parts = scope_parts(lowered(sut, closure))
    assert set(scopes) <= parts
    if closure == "prefill":
        assert tracing.LANE_FREEZE not in parts


@pytest.mark.parametrize("closure", ["decode", "prefill"])
def test_layer_scan_slices_sit_under_layers_not_block(sut, closure):
    text = lowered(sut, closure)
    names = dict(_LOC.findall(text))
    n_layers = sut.cfg.n_layers
    # the scan's per-layer slices: [n_layers, ...] -> [1, ...]
    locs = [names.get(loc, "") for lead, loc in _SLICE.findall(text)
            if int(lead) == n_layers]
    assert locs, "no per-layer slice of a stacked input found"
    for name in locs:
        parts = name.split("/")
        assert tracing.LAYERS in parts and tracing.BLOCK not in parts, name


def loop_ops(hlo: str) -> list:
    """(dtype, element count, opcode) of every array-valued instruction in
    the compiled module's while loops, the computations they call included
    (fusions, nested loops); the entry computation's own ops are left out."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line.strip())
    todo = [m.group(1) for body in comps.values() for ins in body
            for m in re.finditer(r"\bbody=%([\w.\-]+)", ins)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += [c for ins in comps[name] for c in _CALLEE.findall(ins)]
    ops = []
    for name in seen:
        for ins in comps[name]:
            m = _INSTR.match(ins)
            if m:
                dims = [int(d) for d in m.group(2).split(",") if d]
                ops.append((m.group(1), math.prod(dims), m.group(3)))
    return ops


def test_decode_loop_writes_only_cache_rows(sut):
    """The decode step writes one K and one V row per lane and layer: the
    loop holds no select (lane freeze), copy or dynamic-update-slice
    (layer-scan write-back) the size of the stacked cache, only scatters."""
    eng = sut.engine
    sess = eng.begin()
    fn = eng._decode_jits[eng._ladder[-1]]
    hlo = fn.lower(eng.params, sess.cache, sess.tok_buf,
                   sess.state).compile().as_text()
    stacked = math.prod(sess.cache["k"].shape)
    assert sess.cache["k"].shape == (sut.cfg.n_layers, eng.n_slots,
                                     eng.max_seq, sut.cfg.n_kv_heads,
                                     sut.cfg.hd)
    whole = [op for dtype, n, op in loop_ops(hlo)
             if dtype == "f32" and n == stacked]
    assert "scatter" in whole
    assert not {"select", "copy", "dynamic-update-slice"} & set(whole), whole


def engine_spans(trace_dir: str) -> list:
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.SPAN_PREFIX):
                    out.append((ev.start_ns, ev.name, dict(ev.stats)))
    return sorted(out, key=lambda e: e[0])


def serve_traced(eng, reqs, how: str, trace_dir: str) -> int:
    """Serve ``reqs`` under the profiler; returns the chunks dispatched."""
    chunks0 = eng._chunks_dispatched
    jax.profiler.start_trace(trace_dir)
    try:
        if how == "serve":
            eng.serve(reqs)
        else:
            sess = eng.begin()
            now = 0.0
            for r in reqs:
                now = eng.admit(sess, r, now)
            while sess.slots.n_busy:
                now = eng.step(sess, now)
            eng.finish(sess, now)
    finally:
        jax.profiler.stop_trace()
    return eng._chunks_dispatched - chunks0


@pytest.mark.parametrize("how", ["serve", "session"])
def test_engine_spans_per_request_and_per_chunk(sut, how, tmp_path):
    reqs = [Request(rid=10 + i, prompt=[1 + i, 2, 3, 4, 5][:3 + i],
                    max_new=5 + 2 * i, arrival=0.0) for i in range(3)]
    chunks = serve_traced(sut.engine, reqs, how, str(tmp_path))
    spans = engine_spans(str(tmp_path))
    rids = sorted(r.rid for r in reqs)
    for name in ("admit", "prefill", "insert", "first_token"):
        got = sorted(ids["rid"] for _, n, ids in spans
                     if n == "engine." + name)
        assert got == rids, name
    assert sorted(ids["slot"] for _, n, ids in spans
                  if n == "engine.insert") == [0, 1, 2]
    # each request's first token is read after its admission ends
    first = {ids["rid"]: t for t, n, ids in spans
             if n == "engine.first_token"}
    admit = {ids["rid"]: t for t, n, ids in spans if n == "engine.admit"}
    assert all(first[r] > admit[r] for r in rids)
    assert chunks > 0
    for name in ("dispatch", "sync", "books"):
        assert sum(n == "engine." + name for _, n, _ in spans) == chunks
    lengths = [ids["n"] for _, n, ids in spans if n == "engine.dispatch"]
    assert set(lengths) <= set(sut.engine._ladder)


def test_span_is_a_trace_annotation():
    with tracing.span("admit", rid=3) as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)


def test_summary_gives_no_compute_rate():
    text = ServeReport(records={}, n_steps=3).summary()
    assert "tok/s" not in text and "3 decode steps" in text
