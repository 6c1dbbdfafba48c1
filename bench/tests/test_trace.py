"""The trace reduction and the per-layer readers.

Arithmetic on hand-made event lists, and `trace.read` on a small trace
recorded on a TPU v5e (``data/small.xplane.pb.gz``: the 8-layer
granite8b-aimc engine admitting two requests and running one 2-step decode
chunk inside the harness's ``bench.*`` spans, Python tracer off).
"""

import dataclasses
import gzip
import importlib.util
import shutil
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data"
METRICS = Path(__file__).parents[1] / "metrics"


def ev(start, dur, name="op"):
    return (float(start), float(dur), name)


def test_union_merges_overlaps():
    evs = [ev(0, 10), ev(5, 10), ev(20, 5), ev(21, 1), ev(30, 0)]
    assert tr.union_ns(evs) == 15 + 5


def test_clip_and_gaps():
    evs = [ev(0, 10), ev(5, 10), ev(20, 5)]
    assert tr.clip(evs, 8, 22) == [ev(8, 2), ev(8, 7), ev(20, 2)]
    assert tr.gaps(evs, 0, 40) == [(15.0, 5.0), (25.0, 15.0)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_window_busy_top_and_idle():
    t = tr.Trace(
        ops=[ev(0, 400, "%while.2 = (s32[]) while(...)"),
             ev(100, 50, "%fusion.12 = f32[8] fusion(...)"),
             ev(160, 20, "%aimc_matmul_pallas_v2.3 = f32[32,512] "
                         "custom-call(...)"),
             ev(300, 100, "%fusion.7 = f32[8] fusion(...)"),
             ev(950, 200, "%fusion.1 = f32[8] fusion(...)")],
        modules=[],
        spans=[ev(0, 1000, "bench.window"), ev(0, 200, "bench.step"),
               ev(200, 600, "bench.sleep"), ev(900, 100, "bench.step")])
    assert tr.window(t) == (0.0, 1000.0)
    busy, win = tr.busy_and_window_s(t)
    assert busy == pytest.approx(450e-9)     # the last op is cut at 1000
    assert win == pytest.approx(1000e-9)
    top = tr.top_ops(t)
    # the while op keeps only the time its body's ops leave uncovered
    assert top == [["while", pytest.approx(230e-9)],
                   ["fusion", pytest.approx(200e-9)],
                   ["aimc_matmul_pallas_v2", pytest.approx(20e-9)]]
    gaps = tr.idle_gaps(t)
    assert gaps == [["bench.sleep", pytest.approx(550e-9)]]


@dataclasses.dataclass
class FakeWindow:
    served: dict
    counters: dict
    spans: dict
    wall_s: float


@dataclasses.dataclass
class FakeCtx:
    window: FakeWindow
    trace: object
    dims: dict
    engine: dict
    peaks: dict
    notes: list


def reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


DIMS = {"n_layers": 1, "d_model": 512, "n_heads": 4, "n_kv_heads": 1,
        "head_dim": 128, "d_ff": 1024, "vocab": 2048, "tile_rows": 512}
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


def fake_ctx(trace=None, counters=None):
    return FakeCtx(
        window=FakeWindow(served={0: ((1,) * 8, [1] * 4, 4)},
                          counters=counters or {"n_steps": 10,
                                                "n_prefills": 1,
                                                "idle_vectors": 5},
                          spans={"queue_wait_s": [0.001 * i
                                                  for i in range(21)]},
                          wall_s=2.0),
        trace=trace, dims=DIMS, engine={"n_slots": 4, "prompt_pad": 16},
        peaks=PEAKS, notes=[])


def test_readers_without_a_trace():
    ctx = fake_ctx()
    assert reader("batch_occupancy.batch")(ctx) == pytest.approx(87.5)
    assert reader("queue_wait_p95_ms.online")(ctx) == pytest.approx(19.0)
    for name in ("prefill_ms.online", "decode_step_ms.batch",
                 "aimc_mvm_roofline.batch", "device_idle.batch"):
        assert reader(name)(ctx) is None
    from bench.counts import request_work
    ops, flops = request_work(DIMS, 8, 4)
    assert reader("mfu.batch")(ctx) == pytest.approx(
        100 * (ops / 393e12 + flops / 197e12) / 2.0)


def test_roofline_needs_every_call_in_the_trace():
    kern = "%aimc_matmul_pallas_v2.4 = f32[4,512] custom-call(...)"
    calls = 7 * (10 + 1)
    t = tr.Trace(ops=[ev(10 * i, 5, kern) for i in range(calls)],
                 modules=[], spans=[ev(0, 10 * calls, "bench.window")])
    ctx = fake_ctx(trace=t)
    got = reader("aimc_mvm_roofline.batch")(ctx)
    from bench import counts
    want, _ = counts.roofline_seconds(
        counts.aimc_calls(DIMS, 4, 10) + counts.aimc_calls(DIMS, 16, 1),
        PEAKS)
    assert got == pytest.approx(100 * want / (calls * 5e-9))
    t.ops.pop()
    assert reader("aimc_mvm_roofline.batch")(fake_ctx(trace=t)) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    with gzip.open(DATA / "small.xplane.pb.gz") as src, \
            open(d / "small.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.read(str(d))


def test_recorded_trace_reads(recorded):
    assert recorded.ops and recorded.modules
    lo, hi = tr.window(recorded)
    busy, win = tr.busy_and_window_s(recorded)
    assert 0 < busy < win
    labels = [lab for lab, _ in tr.top_ops(recorded)]
    assert "aimc_matmul_pallas_v2" in labels
    # 2 prefills and one 2-step decode chunk, 7 matrices x 8 layers each
    assert sum(1 for e in recorded.ops if tr.is_kernel(e)) == 7 * 8 * 4
    assert tr.idle_gaps(recorded)[0][0] in {"bench.admit", "bench.step",
                                            "bench.sleep"}
    assert {s[2] for s in recorded.spans} >= {"bench.window", "bench.admit",
                                              "bench.step"}
    assert any(m[2].startswith("jit__prefill_fn") for m in recorded.modules)
    assert any(m[2].startswith("jit_traced") for m in recorded.modules)
