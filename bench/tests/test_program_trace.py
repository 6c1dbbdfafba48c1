"""The program's spans and scopes in a trace (`bench.program_trace`) and the
readers of the program's per-layer metrics.

Arithmetic on hand-made events; the XSpace metadata reader against
TensorFlow's own parser; `bench.trace` unchanged on the recorded trace of
the program before it had spans or scopes (``data/small.xplane.pb.gz``);
and the four readers on a trace recorded on a TPU v5e with them
(``data/program.xplane.pb.gz``: ``bench/program_trace.py --workload
glm4-9b-aimc.online --seed 3500000001 --seconds 0.5 --smoke``:
`bench/drivers/open_loop.py` through the engine's session primitives at the
rehearsal's smoke size).
"""

import dataclasses
import gzip
import hashlib
import importlib.util
import json
import shutil
from pathlib import Path

import pytest

from bench import program_trace as pt
from bench import trace as tr
from repro.tracing import SCOPES

DATA = Path(__file__).parent / "data"
METRICS = Path(__file__).parents[1] / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def unpack(name, tmp_path) -> Path:
    dst = tmp_path / name.removesuffix(".gz")
    with gzip.open(DATA / name) as src, open(dst, "wb") as out:
        shutil.copyfileobj(src, out)
    return dst


def digest(x) -> str:
    return hashlib.sha256(json.dumps(x).encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def test_interval_arithmetic():
    a = [(0, 10), (20, 30)]
    b = [(5, 22), (25, 26), (40, 50)]
    assert pt.intersect(a, b) == [(5, 10), (20, 22), (25, 26)]
    assert pt.subtract(a, b) == [(0, 5), (22, 25), (26, 30)]
    assert pt.length(pt.subtract(a, [])) == 20
    assert pt.subtract([(0, 4)], [(0, 4)]) == []


def test_innermost_labels_nested_spans():
    spans = [(0, 100, "engine.admit", {}), (10, 20, "engine.prefill", {}),
             (50, 10, "engine.insert", {}), (200, 50, "engine.sync", {})]
    assert pt.innermost(spans) == {
        "engine.admit": [(0, 10), (30, 50), (60, 100)],
        "engine.prefill": [(10, 30)],
        "engine.insert": [(50, 60)],
        "engine.sync": [(200, 250)]}


def test_first_token_waits_pair_by_rid():
    program = [(0, 10, "engine.admit", {"rid": 1}),
               (5, 10, "engine.admit", {"rid": 2}),
               (30, 2, "engine.dispatch", {"n": 4}),
               (100, 5, "engine.first_token", {"rid": 1}),
               (105, 5, "engine.first_token", {"rid": 2}),
               (0, 1e6, "engine.admit", {"rid": 3})]    # never read
    assert pt.first_token_waits_ms(program, [1, 2, 3]) == [
        pytest.approx(95e-6), pytest.approx(95e-6)]
    assert pt.first_token_waits_ms(program, [2]) == [pytest.approx(95e-6)]


def test_scope_category_and_dtype():
    base = "jit(traced)/while/body/closed_call/layers/while/body/"
    assert pt.category(base + "squeeze:") == "layers"
    assert pt.category(base + "closed_call/block/aimc_mvm/pallas_call:") \
        == "aimc_mvm"
    assert pt.category(base + "closed_call/block/rmsnorm/mul:") == "block"
    assert pt.category("jit(traced)/while/body/closed_call/lane_freeze/"
                       "jit(_where)/select_n:") == "lane_freeze"
    assert pt.category("jit(traced)/while/body/add:") == pt.UNSCOPED
    assert pt.category(None) == pt.UNTAGGED
    assert pt.result_dtype("%dynamic-slice_bitcast_fusion.3 = s8[1,4,512]"
                           "{2,1,0} fusion(...)") == "s8"
    assert pt.result_dtype("%f.1 = (f32[2]{0}, s8[4]{0}) fusion(...)") == \
        "f32"


HAND_OPS = [(0, 100, "%a.1 = f32[8] fusion(...)"),
            (150, 50, "%b.2 = s8[8] fusion(...)"),
            (400, 100, "%c.3 = f32[8] select(...)"),
            (520, 30, "%copy.5 = f32[8] copy(...)"),
            (900, 100, "%a.1 = f32[8] fusion(...)")]


def hand_made_trace():
    """Two decode chunks (programs 11 and 12) and one prefill (13), whose
    first op shares its name with a decode op under another path."""
    ops = HAND_OPS
    t = tr.Trace(ops=ops,
                 modules=[(0, 250, "jit_traced(11)"),
                          (400, 160, "jit_traced(12)"),
                          (900, 100, "jit__prefill_fn(13)")],
                 spans=[(0, 1000, "bench.window"), (600, 200, "bench.sleep")])
    t.program = [(90, 80, "engine.dispatch", {"n": 4}),
                 (200, 150, "engine.sync", {}),
                 (500, 50, "engine.dispatch", {"n": 4})]
    t.scopes = {("11", ops[0][2]): "jit(traced)/layers/while/body/squeeze:",
                ("11", ops[1][2]): "jit(traced)/layers/while/body/squeeze:",
                ("12", ops[2][2]): "jit(traced)/while/body/lane_freeze/"
                                   "select_n:",
                ("13", ops[4][2]): "jit(_prefill_fn)/head/dot_general:"}
    return t


def test_module_self_times_by_program_id():
    t = hand_made_trace()
    total, ops = pt.module_self_times(t)
    assert total == 410
    assert [(name, path) for name, path, _ in ops] == [
        (HAND_OPS[0][2], "jit(traced)/layers/while/body/squeeze:"),
        (HAND_OPS[1][2], "jit(traced)/layers/while/body/squeeze:"),
        (HAND_OPS[2][2], "jit(traced)/while/body/lane_freeze/select_n:"),
        (HAND_OPS[3][2], None)]
    secs = pt.scope_seconds(total, ops)
    assert secs == {"layers": pytest.approx(150e-9),
                    "lane_freeze": pytest.approx(100e-9),
                    pt.UNTAGGED: pytest.approx(30e-9),
                    pt.BETWEEN: pytest.approx(130e-9)}
    assert sum(secs.values()) == pytest.approx(total * 1e-9)
    total, ops = pt.module_self_times(t, pt.PREFILL)
    assert (total, [path for _, path, _ in ops]) == (
        100, ["jit(_prefill_fn)/head/dot_general:"])
    note = pt.scope_note(t)
    assert note.startswith("jit_traced device time by scope, 0.000000 s")
    assert "untagged ops: copy 0.000000 s" in note


def test_host_gap_and_scope_shares_on_hand_made_events():
    t = hand_made_trace()
    idle, dispatches = pt.host_gaps(t)
    # idle: 100-150, 200-400, 500-520, 550-600 and 800-900 (600-800 is a
    # sleep)
    assert idle == [(100, 150), (200, 400), (500, 520), (550, 600),
                    (800, 900)]
    assert len(dispatches) == 2
    split = pt.idle_by_span(t, idle)
    assert split["engine.dispatch"] == pytest.approx(70e-9)
    assert split["engine.sync"] == pytest.approx(150e-9)
    assert split["driver"] == pytest.approx(200e-9)
    ctx = FakeCtx(window=FakeWindow(served={}), trace=t, notes=[])
    assert reader("host_gap_per_chunk_ms.online")(ctx) == pytest.approx(
        420 / 2 * 1e-6)
    assert reader("lane_freeze_share.batch")(ctx) == pytest.approx(
        100 * 100 / 410)
    assert reader("layer_stack_share.batch")(ctx) == pytest.approx(
        100 * 150 / 410)
    assert "f32" in ctx.notes[-1] and "s8" in ctx.notes[-1]


@dataclasses.dataclass
class FakeWindow:
    served: dict


@dataclasses.dataclass
class FakeCtx:
    window: FakeWindow
    trace: object
    notes: list


def test_main_refuses_a_host_without_a_tpu(capsys):
    """The traced-run bridge, like `bench/run.py`, prints no result line
    where JAX finds no TPU, ``--smoke`` included."""
    for extra in ([], ["--smoke"]):
        assert pt.main(["--workload", "glm4-9b-aimc.online", "--seed",
                        "3500000001", "--seconds", "0.1"] + extra) == 2
        assert capsys.readouterr().out == ""


def test_readers_without_program_data():
    t = tr.Trace(ops=[(0, 10, "%a.1 = f32[8] fusion(...)")], modules=[],
                 spans=[(0, 20, "bench.window")])
    for name in ("first_token_wait_p95_ms.online",
                 "host_gap_per_chunk_ms.online", "lane_freeze_share.batch",
                 "layer_stack_share.batch"):
        for trace in (None, t):
            ctx = FakeCtx(window=FakeWindow(served={1: ((1,), [2], 1)}),
                          trace=trace, notes=[])
            assert reader(name)(ctx) is None, name


# ---------------------------------------------------------------------------
# recorded traces
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return unpack("small.xplane.pb.gz", tmp_path_factory.mktemp("small"))


def test_trace_outputs_unchanged(small):
    """`bench.trace.read` and its reductions give what they gave before the
    program had spans and scopes, and attaching the program's data leaves
    them as they are."""
    t = tr.read(str(small.parent))
    want = {"ops": "7aa77355cb2b93bb", "modules": "12d9ec7238cc8fae",
            "spans": "be3974c153b16da9", "top_ops": "049bcd99f1dea048",
            "idle_gaps": "c12585079ff2360b"}
    for trace in (t, pt.attach(t, str(small))):
        got = {"ops": digest(trace.ops), "modules": digest(trace.modules),
               "spans": digest(trace.spans),
               "top_ops": digest(tr.top_ops(trace)),
               "idle_gaps": digest(tr.idle_gaps(trace))}
        assert got == want
    # the parent program marked nothing: no spans, tf_op paths without scopes
    assert t.program == []
    assert t.scopes and not any(
        pt.category(p) != "unscoped" for p in t.scopes.values())


def test_metadata_reader_agrees_with_tensorflow(small):
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    data = small.read_bytes()
    space = xplane_pb2.XSpace()
    space.ParseFromString(data)
    mine = pt.event_metadata(data)
    assert set(mine) == {p.name for p in space.planes}
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        want = {}
        for key, md in plane.event_metadata.items():
            stats = {}
            for s in md.stats:
                kind = s.WhichOneof("value")
                stats[names.get(s.metadata_id, str(s.metadata_id))] = (
                    names.get(s.ref_value, "") if kind == "ref_value"
                    else getattr(s, kind))
            want[key] = (md.name, md.display_name, stats)
        assert mine[plane.name] == want, plane.name


@pytest.fixture(scope="module")
def program(tmp_path_factory):
    path = unpack("program.xplane.pb.gz", tmp_path_factory.mktemp("program"))
    return pt.attach(tr.read(str(path.parent)), str(path))


def test_recorded_engine_spans(program):
    by_name = {}
    for _, _, name, ids in program.program:
        by_name.setdefault(name, []).append(ids)
    rids = sorted(ids["rid"] for ids in by_name["engine.admit"])
    assert rids == list(range(17))
    for name in ("engine.prefill", "engine.insert", "engine.first_token"):
        assert sorted(ids["rid"] for ids in by_name[name]) == rids, name
    # each `bench.step` of the open loop is one chunk: sent, synced, booked
    steps = sum(1 for s in program.spans if s[2] == "bench.step")
    for name in ("engine.dispatch", "engine.sync", "engine.books"):
        assert len(by_name[name]) == steps == 18, name
    assert {ids["n"] for ids in by_name["engine.dispatch"]} == {1, 2, 4}


def test_recorded_op_scopes(program):
    total, ops = pt.module_self_times(program)
    assert set(pt.scope_seconds(total, ops)) == set(SCOPES) | {
        pt.UNSCOPED, pt.UNTAGGED, pt.BETWEEN}
    # every crossbar kernel call, decode and prefill, is under aimc_mvm
    kernels = [path for module in (pt.DECODE, pt.PREFILL)
               for name, path, _ in pt.module_self_times(program, module)[1]
               if tr.is_kernel((0, 0, name))]
    lo, hi = tr.window(program)
    assert len(kernels) == sum(
        1 for e in tr.clip(program.ops, lo, hi) if tr.is_kernel(e))
    assert kernels and all(pt.category(p) == "aimc_mvm" for p in kernels)
    # the layer scan's own time is its slices of the stacked inputs and its
    # stacking of the new cache
    stack = [(tr.op_label((0, 0, name)), ns) for name, path, ns in ops
             if pt.category(path) == "layers"]
    sliced = sum(ns for lab, ns in stack if "slice" in lab)
    assert sliced > 0.99 * sum(ns for _, ns in stack)


def test_readers_on_the_recorded_trace(program):
    from bench.traffic import percentile
    ctx = FakeCtx(window=FakeWindow(served={r: None for r in range(17)}),
                  trace=program, notes=[])
    waits = pt.first_token_waits_ms(program.program, range(17))
    assert len(waits) == 17
    got = reader("first_token_wait_p95_ms.online")(ctx)
    assert got == pytest.approx(percentile(waits, 95))
    assert got == pytest.approx(16.475488, rel=1e-6)

    idle, _ = pt.host_gaps(program)
    assert sum(pt.idle_by_span(program, idle).values()) == pytest.approx(
        pt.length(idle) * 1e-9)
    assert reader("host_gap_per_chunk_ms.online")(ctx) == pytest.approx(
        pt.length(idle) / 18 * 1e-6)
    assert pt.length(idle) * 1e-9 == pytest.approx(0.517342, rel=1e-5)

    total, ops = pt.module_self_times(program)
    secs = pt.scope_seconds(total, ops)
    total *= 1e-9
    assert total == pytest.approx(0.004106, rel=1e-3)
    # the decode module's ops with no tf_op path are its loops' own time
    # and copies
    assert secs[pt.UNTAGGED] == pytest.approx(0.000290, rel=1e-2)
    freeze = reader("lane_freeze_share.batch")(ctx)
    stack = reader("layer_stack_share.batch")(ctx)
    assert freeze == pytest.approx(100 * secs["lane_freeze"] / total)
    assert stack == pytest.approx(100 * secs["layers"] / total)
    assert (freeze, stack) == (pytest.approx(0.52, abs=1e-2),
                               pytest.approx(32.87, abs=1e-2))
    assert ctx.notes[-1].startswith("layer_stack:")
