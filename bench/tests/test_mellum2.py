"""The sparse-expert cell's yardstick: `bench.counts_moe` against hand
counts and against `bench.counts` where the two overlap, and the readers of
``expert_mvm_roofline.codectx`` and ``mfu.codectx`` on synthetic windows."""

import importlib.util
import types
from pathlib import Path

import pytest

from bench import counts, counts_moe
from bench import trace as tr
from bench.drivers.common import Window

METRICS = Path(__file__).parents[1] / "metrics"
GRANITE = {"n_layers": 12, "d_model": 4096, "n_heads": 32, "n_kv_heads": 8,
           "head_dim": 128, "d_ff": 14336, "vocab": 49152, "tile_rows": 512}
GLM = {"n_layers": 10, "d_model": 4096, "n_heads": 32, "n_kv_heads": 2,
       "head_dim": 128, "d_ff": 13696, "vocab": 151552, "qkv_bias": True,
       "tile_rows": 512}
MELLUM = {"n_layers": 8, "d_model": 2304, "n_heads": 32, "n_kv_heads": 4,
          "head_dim": 128, "d_ff": 896, "vocab": 98304, "tile_rows": 512}
DENSE = {"n_experts": 0, "top_k": 1, "window": 0,
         "layer_types": ["full_attention"]}
PEAKS = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_moe_of_reads_the_configuration():
    moe = counts_moe.moe_of("mellum2-12b-aimc")
    assert (moe["n_experts"], moe["top_k"], moe["window"]) == (64, 8, 1024)
    assert moe["layer_types"][3] == "full_attention"
    assert moe["layer_types"][:3] == ["sliding_attention"] * 3


@pytest.mark.parametrize("dims", [GRANITE, GLM])
@pytest.mark.parametrize("prompt,n", [(3, 2), (160, 192), (512, 1)])
def test_dense_request_work_unchanged(dims, prompt, n):
    assert (counts_moe.request_work(dims, DENSE, prompt, n)
            == counts.request_work(dims, prompt, n))


def test_request_work_by_hand():
    d = {"n_layers": 2, "d_model": 2, "n_heads": 1, "n_kv_heads": 1,
         "head_dim": 2, "d_ff": 3, "vocab": 5}
    moe = {"n_experts": 4, "top_k": 2, "window": 2,
           "layer_types": ["sliding_attention", "full_attention"]}
    ops, flops = counts_moe.request_work(d, moe, prompt_len=3, n_tokens=2)
    # per token per layer: q k v o 2*(4*4) = 32; two experts 2*2*(6+6+6) = 72
    assert ops == (32 + 72) * 2 * 4          # 2 layers, 4 positions
    # router 2*2*4 per token per layer; head 2*2*5 per generated token;
    # keys seen: sliding 1+2+2+2 = 7, full 1+2+3+4 = 10; 4 * keys * 1 * 2
    assert flops == 16 * 2 * 4 + 20 * 2 + 4 * (7 + 10) * 2


def test_expert_calls_by_hand():
    calls = counts_moe.expert_calls(MELLUM, counts_moe.moe_of(
        "mellum2-12b-aimc"), tokens=32, calls=3)
    assert len(calls) == 2 * 8 * 3
    (ops_gu, bytes_gu), (ops_d, bytes_d) = calls[:2]
    rows = 32 * 8
    assert ops_gu == 2 * 2 * rows * 2304 * 896
    assert ops_d == 2 * rows * 896 * 2304
    # 64 experts' codes and scales (5 and 2 row blocks), rows in and out
    assert bytes_gu == (2 * 64 * (2304 * 896 + 5 * 896 * 4)
                        + rows * 2304 * 4 + 2 * rows * 896 * 4)
    assert bytes_d == (64 * (896 * 2304 + 2 * 2304 * 4)
                       + rows * 896 * 4 + rows * 2304 * 4)
    t, bound = counts.roofline_seconds(calls[:2], PEAKS)
    assert bound == "bytes"


def _ctx(events, n_steps=3, n_prefills=1):
    window = Window(served={}, e2e={},
                    counters={"n_steps": n_steps, "n_prefills": n_prefills})
    trace = tr.Trace(ops=events, modules=[],
                     spans=[(0.0, 1e12, "bench.window")])
    return types.SimpleNamespace(
        window=window, trace=trace, dims=MELLUM, peaks=PEAKS, notes=[],
        engine={"n_slots": 32, "prompt_pad": 1536})


def _kernel_events(n, dur_ns):
    return [(float(i * 1e7), float(dur_ns),
             f"%aimc_expert_pallas.{i} = f32[2,704,896] custom-call(...)")
            for i in range(n)]


def test_expert_roofline_needs_every_call():
    read = reader("expert_mvm_roofline.codectx")
    expect = 2 * 8 * (3 + 1)
    ctx = _ctx(_kernel_events(expect - 1, 1e6))
    assert read(ctx) is None
    assert "not reported" in ctx.notes[0]
    # the dense kernel's events are not this kernel's
    dense = [(0.0, 5e5, "%aimc_matmul_pallas_v2.3 = f32[32,512] "
                        "custom-call(...)")]
    assert read(_ctx(dense)) is None


def test_expert_roofline_share():
    read = reader("expert_mvm_roofline.codectx")
    expect = 2 * 8 * (3 + 1)
    moe = counts_moe.moe_of("mellum2-12b-aimc")
    least, _ = counts.roofline_seconds(
        counts_moe.expert_calls(MELLUM, moe, 32, 3)
        + counts_moe.expert_calls(MELLUM, moe, 1536, 1), PEAKS)
    # kernel time at exactly the least time reads 100%; twice it, 50%
    per_call = least / expect * 1e9
    assert read(_ctx(_kernel_events(expect, per_call))) == pytest.approx(100)
    share = read(_ctx(_kernel_events(expect, 2 * per_call)))
    assert share == pytest.approx(50) and share <= 100


def test_mfu_counts_routed_work():
    read = reader("mfu.codectx")
    served = {0: (list(range(1100)), list(range(96)), 96),
              1: (list(range(300)), list(range(20)), 20)}
    ctx = _ctx([])
    ctx.window = Window(served=served, e2e={}, counters={}, wall_s=2.0)
    moe = counts_moe.moe_of("mellum2-12b-aimc")
    ops = flops = 0
    for prompt, toks, _ in served.values():
        o, f = counts_moe.request_work(MELLUM, moe, len(prompt), len(toks))
        ops, flops = ops + o, flops + f
    want = 100 * (ops / PEAKS["int8_ops"] + flops / PEAKS["bf16_flops"]) / 2
    assert read(ctx) == pytest.approx(want)
    assert 0 < read(ctx) <= 100
    ctx.window = Window(served={}, e2e={}, counters={}, wall_s=2.0)
    assert read(ctx) is None


def test_rehearse_runs_the_new_cells(monkeypatch, capsys):
    """Both new cells through `bench.run.execute` on the CPU at smoke
    size, the check included."""
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: None)
    from bench import rehearse
    assert rehearse.main(["--seconds", "1", "--workload",
                          "mellum2-12b-aimc.codectx", "--workload",
                          "granite8b-aimc.online"]) == 0
    out = capsys.readouterr().out
    assert "mellum2-12b-aimc.codectx (CPU, smoke size)" in out
    assert "granite8b-aimc.online (CPU, smoke size)" in out
