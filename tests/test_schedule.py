"""Multi-core scheduler (core/schedule.py).

The load-bearing invariants: column splits are exact, every paper multi-core
mapping executes numerically equal to the single-core programmed path,
per-core CM_* ledgers reconcile with the single-core program totals, the two
dataflow latency laws hold, and the schedule-modeled latency agrees with
`costmodel.evaluate()` on the matching Workload IR (the measured-vs-predicted
consistency the benchmarks report).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isa
from repro.core.aimc import AimcConfig, aimc_apply, program_linear
from repro.core.costmodel import HIGH_POWER, evaluate
from repro.core.program import MappingPlan, program_model
from repro.core.schedule import (CoreSchedule, OverlapRoofline, Shard,
                                 cnn_schedule, lstm_schedule, mlp_schedule,
                                 pipeline_run, pipelined_latency,
                                 select_columns, sequential_latency)
from repro.core.workloads import lstm_workloads, mlp_workloads
from repro.launch.mesh import make_mesh
from repro.models import paper_nets as pn

CFG = AimcConfig(tile_rows=128, impl="ref")


# ---------------------------------------------------------------------------
# select_columns: exactness
# ---------------------------------------------------------------------------

def test_select_columns_contiguous_and_interleaved():
    w = jax.random.normal(jax.random.PRNGKey(0), (300, 200)) * 0.05
    st = program_linear(w, CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 300))
    y = aimc_apply(st, x, CFG)
    sub = select_columns(st, [(0, 77)])
    np.testing.assert_array_equal(np.asarray(aimc_apply(sub, x, CFG)),
                                  np.asarray(y[:, :77]))
    gaps = select_columns(st, [(50, 100), (150, 200)])
    np.testing.assert_array_equal(
        np.asarray(aimc_apply(gaps, x, CFG)),
        np.asarray(jnp.concatenate([y[:, 50:100], y[:, 150:200]], -1)))


def test_select_columns_validates():
    st = program_linear(jnp.ones((64, 32)) * 0.1, CFG)
    with pytest.raises(ValueError):
        select_columns(st, [(0, 40)])            # past logical n
    with pytest.raises(ValueError):
        select_columns(st, [(0, 16), (8, 24)])   # overlap


# ---------------------------------------------------------------------------
# paper mappings: multi-core == single-core (the acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cores", [2, 4])
def test_mlp_multicore_equals_single_core(cores):
    params = pn.mlp_init(jax.random.PRNGKey(0), n=128)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 128))
    y1, _ = pn.mlp_forward_multicore(params, x, CFG, cores=1)
    ym, sched = pn.mlp_forward_multicore(params, x, CFG, cores=cores)
    assert sched.n_cores == cores
    np.testing.assert_array_equal(np.asarray(ym), np.asarray(y1))


@pytest.mark.parametrize("cores", [2, 5])
def test_lstm_multicore_equals_single_core(cores):
    nh = 64
    params = pn.lstm_init(jax.random.PRNGKey(0), nh, x_dim=16, y_dim=12)
    xs = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 16))
    y1, _ = pn.lstm_forward_multicore(params, xs, nh, CFG, cores=1)
    ym, sched = pn.lstm_forward_multicore(params, xs, nh, CFG, cores=cores)
    assert sched.n_cores == cores
    np.testing.assert_array_equal(np.asarray(ym), np.asarray(y1))


def test_cnn_pipeline_equals_single_core_ctx_path():
    params = pn.cnn_init(jax.random.PRNGKey(0), "F", img=64, n_classes=10)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64, 3))
    y_ctx, _ = pn.cnn_forward(params, x, "F", CFG)
    y_mc, sched = pn.cnn_forward_multicore(params, x, "F", CFG)
    assert sched.pipelined and sched.n_cores == 5
    np.testing.assert_array_equal(np.asarray(y_mc), np.asarray(y_ctx))


def test_multicore_matches_under_jit():
    """The compiled column-split schedule equals the compiled single-core
    path bit for bit. The reference is compiled too: under jit XLA folds
    the ADC step into the DAC scale (``adc_step * amax / 127``), so an
    eager reference rounds the dequant scale differently."""
    params = pn.mlp_init(jax.random.PRNGKey(0), n=128)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128))
    _, sched = pn.mlp_forward_multicore(params, x, CFG, cores=4)
    f = jax.jit(lambda v: pn.mlp_forward_multicore(
        params, v, CFG, schedule=sched)[0])
    one = jax.jit(lambda v: pn.mlp_forward_multicore(
        params, v, CFG, cores=1)[0])
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(one(x)))


# ---------------------------------------------------------------------------
# per-core ledgers reconcile with the single-core program totals
# ---------------------------------------------------------------------------

def test_unsplit_ledgers_sum_to_program_totals():
    """Layer-per-core mappings (no column split): per-core CM_* ledgers sum
    EXACTLY to the single-core program's per-vector counts."""
    params = pn.mlp_init(jax.random.PRNGKey(0), n=128)
    prog = pn.mlp_program(params, CFG)
    for cores in (1, 2):
        sched = mlp_schedule(prog, cores)
        assert sched.ledger_totals() == prog.mvm_counts()


def test_from_program_ledgers_sum_to_program_totals():
    params = {"blocks": {"wq": jnp.ones((2, 64, 32)) * 0.1,
                         "wo": jnp.ones((2, 32, 64)) * 0.1}}
    prog = program_model(params, MappingPlan(n_contexts=2), CFG)
    sched = CoreSchedule.from_program(prog)
    assert sched.n_cores == 2
    assert sched.ledger_totals() == prog.mvm_counts()
    # round-robin contexts alternate cores -> the hand-off edge is charged
    assert sum(led.comm_bytes for led in sched.ledgers()) > 0


def test_column_split_ledgers_partition_dequeue_and_duplicate_queue():
    """Column splits partition the bit lines (dequeue sums exactly) but every
    core queues the FULL input vector (queue duplicates by the split factor)
    — the paper's case-4 multi-core queue tax, quantified."""
    params = pn.mlp_init(jax.random.PRNGKey(0), n=128)
    prog = pn.mlp_program(params, CFG)
    sched = mlp_schedule(prog, 4)
    tot, ref = sched.ledger_totals(), prog.mvm_counts()
    assert tot.dequeue == ref.dequeue
    assert tot.dequeue_bytes == ref.dequeue_bytes
    assert tot.queue == 2 * ref.queue            # each layer split 2-ways
    assert tot.process == 2 * ref.process


def test_cnn_ledger_scales_with_positions():
    params = pn.cnn_init(jax.random.PRNGKey(0), "F", img=64, n_classes=10)
    prog = pn.cnn_program(params, "F", CFG)
    sched = cnn_schedule(prog, pn.CNN_SPECS["F"], img=64)
    want = isa.total(
        isa.mvm_counts(prog[sh.name].k, prog[sh.name].n,
                       CFG.tile_rows).scaled(sh.count)
        for sh in sched.shards)
    got = sched.ledger_totals()
    assert (got.queue, got.process, got.dequeue) == (
        want.queue, want.process, want.dequeue)


# ---------------------------------------------------------------------------
# dataflow latency laws
# ---------------------------------------------------------------------------

def test_latency_laws_on_synthetic_stage_times():
    phases = [(3.0, 1.0), (2.0,), (5.0, 4.0, 1.0)]
    assert sequential_latency(phases) == 3.0 + 2.0 + 5.0   # sum of phase maxes
    assert pipelined_latency(phases) == 5.0                # slowest stage
    assert sequential_latency([]) == 0.0
    assert pipelined_latency([()]) == 0.0


def test_overlap_roofline_recovers_exact_constants():
    # synthetic step times generated FROM the law must fit back exactly
    truth = OverlapRoofline(t_step_s=2.0e-3, t_round_s=8.0e-3)
    times = {k: truth.predict_step_s(k) for k in (1, 2, 4, 8)}
    fit = OverlapRoofline.fit(times)
    assert abs(fit.t_step_s - truth.t_step_s) < 1e-12
    assert abs(fit.t_round_s - truth.t_round_s) < 1e-12
    assert abs(fit.predict_step_s(16) - (2.0e-3 + 8.0e-3 / 16)) < 1e-12
    # speedup 1 -> 8: (2+8)/(2+1) ms
    assert abs(fit.speedup(1, 8) - 10.0 / 3.0) < 1e-9
    assert all(r < 1e-9 for r in fit.residuals(times).values())


def test_overlap_roofline_least_squares_and_guards():
    # noisy over-determined system: fit minimizes residuals, stays close
    truth = OverlapRoofline(t_step_s=1.0e-3, t_round_s=4.0e-3)
    noise = {1: 1.02, 2: 0.97, 4: 1.03, 8: 0.99}
    times = {k: truth.predict_step_s(k) * noise[k] for k in noise}
    fit = OverlapRoofline.fit(times)
    assert max(fit.residuals(times).values()) < 0.1
    # monotone: bigger chunks never predict slower steps
    preds = [fit.predict_step_s(k) for k in (1, 2, 4, 8, 16)]
    assert all(a >= b for a, b in zip(preds, preds[1:]))
    with pytest.raises(ValueError):
        OverlapRoofline.fit({4: 1.0e-3})
    with pytest.raises(ValueError):
        fit.predict_step_s(0)
    # a fit tilted negative by noise clamps to 0, never negative time
    neg = OverlapRoofline.fit({1: 1.0e-3, 8: 2.0e-3})
    assert neg.t_step_s >= 0.0 and neg.t_round_s >= 0.0


def test_schedule_latency_uses_the_right_law():
    params = pn.mlp_init(jax.random.PRNGKey(0), n=128)
    prog = pn.mlp_program(params, CFG)
    seq = mlp_schedule(prog, 2)
    times = seq.phase_times(HIGH_POWER)
    assert seq.modeled_latency(HIGH_POWER) == sequential_latency(times)
    pipe = CoreSchedule(prog, seq.shards, pipelined=True)
    assert pipe.modeled_latency(HIGH_POWER) == pipelined_latency(times)
    assert pipe.modeled_latency(HIGH_POWER) <= seq.modeled_latency(HIGH_POWER)


@pytest.mark.parametrize("cores,case", [(1, "ana_case1"), (2, "ana_case3"),
                                        (4, "ana_case4")])
def test_mlp_schedule_latency_matches_costmodel(cores, case):
    """The executable schedule and the hand-written Workload IR are two
    descriptions of ONE mapping: priced through the shared accounting they
    must agree exactly."""
    n = 128
    params = pn.mlp_init(jax.random.PRNGKey(0), n=n)
    prog = pn.mlp_program(params, _tile_cfg(n))
    sched = mlp_schedule(prog, cores)
    want = evaluate(mlp_workloads(n)[case], HIGH_POWER).time_s
    got = sched.modeled_latency(HIGH_POWER)
    assert abs(got - want) <= 1e-9 * want


@pytest.mark.parametrize("cores,case", [(1, "ana_case2"), (2, "ana_case3"),
                                        (5, "ana_case4")])
def test_lstm_schedule_latency_matches_costmodel(cores, case):
    nh = 64
    params = pn.lstm_init(jax.random.PRNGKey(0), nh)
    kin = nh + 50
    prog = pn.lstm_program(params, _tile_cfg(kin + 50))
    sched = lstm_schedule(prog, cores, nh)
    want = evaluate(lstm_workloads(nh)[case], HIGH_POWER).time_s
    got = sched.modeled_latency(HIGH_POWER)
    assert abs(got - want) <= 1e-9 * want


def _tile_cfg(tile_rows: int) -> AimcConfig:
    """AimcConfig whose word lines match a Workload's per-case tile_rows."""
    return AimcConfig(tile_rows=tile_rows, tile_cols=4096, impl="ref")


# ---------------------------------------------------------------------------
# schedule construction validation
# ---------------------------------------------------------------------------

def test_rejects_partial_or_mixed_covers():
    prog = pn.mlp_program(pn.mlp_init(jax.random.PRNGKey(0), n=128), CFG)
    with pytest.raises(ValueError):               # half the columns missing
        CoreSchedule(prog, [Shard("fc1", 0, 0, cols=((0, 64),)),
                            Shard("fc2", 0, 1)])
    with pytest.raises(ValueError):               # full + split mixed
        CoreSchedule(prog, [Shard("fc1", 0, 0, cols=((0, 64),)),
                            Shard("fc1", 1, 0),
                            Shard("fc2", 0, 1)])
    with pytest.raises(KeyError):                 # unmapped matrix
        CoreSchedule(prog, [Shard("nope", 0, 0)])


def test_pipeline_run_preserves_values():
    stages = [lambda x: x + 1.0, lambda x: x * 2.0, lambda x: x - 3.0]
    outs, times = pipeline_run(stages, [jnp.zeros(4), jnp.ones(4)])
    np.testing.assert_array_equal(np.asarray(outs[0]),
                                  np.full(4, (0 + 1) * 2 - 3.0))
    np.testing.assert_array_equal(np.asarray(outs[1]),
                                  np.full(4, (1 + 1) * 2 - 3.0))
    assert len(times) == 3 and all(t >= 0 for t in times)


# ---------------------------------------------------------------------------
# launcher wiring: make_step accepts a CoreSchedule (column-sharded serving)
# ---------------------------------------------------------------------------

def test_make_step_accepts_schedule_and_decodes():
    """The full serving wiring: program with 2 contexts -> CoreSchedule ->
    make_step column-shards the installed states (shard_aimc_states) and the
    jitted decode step runs against them."""
    import dataclasses

    from repro.configs import ShapeCell, get_arch
    from repro.launch.shardings import to_named
    from repro.launch.steps import make_step
    from repro.models.layers import Execution

    spec = get_arch("granite_8b")
    spec = dataclasses.replace(spec, model_cfg=spec.smoke_cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    exe = Execution(mode="aimc", aimc=CFG, compute_dtype="float32",
                    programmed=True)
    with jax.set_mesh(mesh):
        model = spec.model_module()
        params = model.init(jax.random.PRNGKey(0), spec.smoke_cfg)
        prog = program_model(params, MappingPlan(n_contexts=2), CFG)
        sched = CoreSchedule.from_program(prog)
        cell = ShapeCell("tiny_dec", seq_len=32, global_batch=2,
                         kind="decode")
        bundle = make_step(spec, cell, mesh, exe, program=sched)
        assert bundle.schedule is sched
        step = jax.jit(bundle.fn,
                       in_shardings=to_named(bundle.in_shardings, mesh),
                       out_shardings=to_named(bundle.out_shardings, mesh))
        cache = model.init_cache(spec.smoke_cfg, 2, 32, jnp.float32)
        toks = jnp.ones((2, 1), jnp.int32)
        for _ in range(2):
            toks, cache = step(prog.install(params), cache, toks)
        assert toks.shape == (2, 1)
        assert int(cache["len"][0]) == 2


def test_shard_aimc_states_rewrites_only_state_leaves():
    from jax.sharding import PartitionSpec as P

    from repro.launch.shardings import get_param_specs, shard_aimc_states

    params = {"blocks": {"wq": jnp.ones((64, 128)) * 0.1,
                         "ln": jnp.ones((64,))}}
    prog = program_model(params, MappingPlan(), CFG)
    installed_shape = jax.eval_shape(lambda: prog.install(params))
    mesh = make_mesh((1, 1), ("data", "model"))
    pspecs = get_param_specs(installed_shape, mesh)
    sharded = shard_aimc_states(pspecs, installed_shape, mesh)
    st = sharded["blocks"]["wq"]
    assert st.w_q == P(None, None, "model")     # bit lines over model
    assert st.s_w == P(None, "model")
    assert sharded["blocks"]["ln"] == pspecs["blocks"]["ln"]  # untouched


# ---------------------------------------------------------------------------
# mesh execution
# ---------------------------------------------------------------------------

def test_apply_sharded_matches_apply_on_mesh():
    params = pn.mlp_init(jax.random.PRNGKey(0), n=256)
    prog = pn.mlp_program(params, CFG)
    sched = mlp_schedule(prog, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256))
    mesh = make_mesh((1, 1), ("data", "model"))
    y_sh = sched.apply_sharded("fc1", x, mesh, axis="model")
    np.testing.assert_array_equal(np.asarray(y_sh),
                                  np.asarray(sched.apply("fc1", x)))


def test_apply_sharded_rejects_full_shards():
    prog = pn.mlp_program(pn.mlp_init(jax.random.PRNGKey(0), n=128), CFG)
    sched = mlp_schedule(prog, 2)
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError):
        sched.apply_sharded("fc1", jnp.ones((2, 128)), mesh)


@pytest.mark.slow
def test_apply_sharded_across_real_devices():
    """The shard_map path with one core per REAL device: forced 2-device CPU
    in a subprocess (XLA device count is fixed at backend init)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=2 "
            + os.environ.get("XLA_FLAGS", ""))
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.aimc import AimcConfig
        from repro.core.schedule import mlp_schedule
        from repro.launch.mesh import make_mesh
        from repro.models import paper_nets as pn
        assert jax.device_count() == 2, jax.devices()
        cfg = AimcConfig(tile_rows=128, impl="ref")
        params = pn.mlp_init(jax.random.PRNGKey(0), n=256)
        prog = pn.mlp_program(params, cfg)
        sched = mlp_schedule(prog, 4)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 256))
        mesh = make_mesh((1, 2), ("data", "model"))
        y = sched.apply_sharded("fc1", x, mesh, axis="model")
        np.testing.assert_array_equal(np.asarray(y),
                                      np.asarray(sched.apply("fc1", x)))
        print("MULTIDEVICE_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        ["src", env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "MULTIDEVICE_OK" in proc.stdout
