"""Sharded multi-device serving (runtime/engine.ShardedServeEngine).

The contracts pinned here (ISSUE 5 acceptance criteria):
  * the sharded engine's decode output is BIT-EQUAL to the single-device
    `ServeEngine` on the same trace — on a unit mesh in-process, and on a
    forced 2-device host-platform mesh in a subprocess (both the
    data-sharded and the model-column-sharded placements), for a
    transformer AND a recurrent arch;
  * aggregated per-request/per-core CM_* ledgers reconcile exactly against
    ``program.mvm_counts()`` (`batcher.reconcile_cores`);
  * shapes stay jit-stable: warmup compiles each closure once, serving
    never recompiles (committed-buffer discipline included);
  * `CoreSchedule.mesh_placement`/`device_ledgers` fold virtual cores onto
    mesh devices without creating or losing traffic;
  * `serve_engine_param_specs` column-shards only `AimcLinearState` leaves;
  * `launch.serve.parse_mesh` accepts both mesh syntaxes;
  * `benchmarks.run.write_report` refuses to clobber a complete artifact
    with a partial (crashed sub-bench) run.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.core.aimc import AimcConfig
from repro.core.program import MappingPlan, program_model
from repro.core.schedule import CoreSchedule
from repro.launch.mesh import make_mesh
from repro.models.layers import Execution
from repro.runtime.batcher import (poisson_trace, reconcile, reconcile_cores,
                                   request_core_ledgers, synchronized_trace)
from repro.runtime.engine import ServeEngine, ShardedServeEngine

EXE = Execution(compute_dtype="float32")


def _programmed_setup(arch="granite-8b", n_contexts=2):
    spec = get_arch(arch)
    cfg = spec.smoke_cfg
    model = spec.model_module()
    params = model.init(jax.random.PRNGKey(0), cfg)
    aimc = AimcConfig(impl="ref", input_scale=0.1)
    exe = Execution(mode="aimc", aimc=aimc, compute_dtype="float32",
                    programmed=True)
    program = program_model(params, MappingPlan(n_contexts=n_contexts), aimc,
                            jax.random.PRNGKey(2))
    return (spec, cfg, model, program.install(params), exe, program,
            CoreSchedule.from_program(program))


# ---------------------------------------------------------------------------
# unit-mesh equivalence (in-process; the mesh machinery with 1 device)
# ---------------------------------------------------------------------------

def test_sharded_equals_plain_on_unit_mesh_programmed():
    spec, cfg, model, params, exe, program, sched = _programmed_setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    kw = dict(n_slots=2, prompt_pad=8, max_seq=20, family=spec.family,
              module=spec.module, program=program, schedule=sched)
    plain = ServeEngine(model, cfg, exe, params, **kw)
    plain.warmup()
    sharded = ShardedServeEngine(model, cfg, exe, params, mesh=mesh, **kw)
    assert sharded.warmup() == {"prefill": 1, "insert": 1, "decode": 1}
    reqs = poisson_trace(6, rate=300.0, seed=6, prompt_len=(3, 8),
                         max_new=(1, 5), vocab=cfg.vocab)
    r1 = plain.serve(list(reqs))
    r2 = sharded.serve(list(reqs))
    for r in reqs:
        assert r1.tokens(r.rid) == r2.tokens(r.rid), \
            f"req {r.rid} diverged on the unit mesh"
    # serving the ragged trace must not have recompiled anything
    assert sharded.compile_counts() == {"prefill": 1, "insert": 1,
                                        "decode": 1}
    # books close: request ledgers, and their per-core split
    assert r2.observed_vectors == r2.useful_vectors
    led_sum, static = reconcile(program, r2.records, r2.observed_vectors)
    assert led_sum == static
    core_sum, sched_total = reconcile_cores(sched, r2.records,
                                            r2.observed_vectors)
    assert core_sum == sched_total
    assert sched_total == program.mvm_counts().scaled(r2.observed_vectors)


def test_sharded_recurrent_on_unit_mesh():
    spec = get_arch("xlstm-350m")
    cfg = spec.smoke_cfg
    model = spec.model_module()
    params = model.init(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    kw = dict(n_slots=2, prompt_pad=6, max_seq=16, family=spec.family,
              module=spec.module, cache_dtype=jnp.float32)
    plain = ServeEngine(model, cfg, EXE, params, **kw)
    plain.warmup()
    sharded = ShardedServeEngine(model, cfg, EXE, params, mesh=mesh, **kw)
    sharded.warmup()
    reqs = synchronized_trace(3, prompt_len=6, max_new=5, seed=7,
                              vocab=cfg.vocab)
    r1 = plain.serve(list(reqs))
    r2 = sharded.serve(list(reqs))
    for r in reqs:
        assert r1.tokens(r.rid) == r2.tokens(r.rid)
    assert sharded.compile_counts() == {"prefill": 1, "insert": 1,
                                        "decode": 1}


# ---------------------------------------------------------------------------
# per-core ledger aggregation + mesh placement of schedule cores
# ---------------------------------------------------------------------------

def test_request_core_ledgers_split_and_reconcile():
    spec, cfg, model, params, exe, program, sched = _programmed_setup()
    eng = ServeEngine(model, cfg, exe, params, n_slots=2, prompt_pad=8,
                      max_seq=20, family=spec.family, module=spec.module,
                      program=program, schedule=sched)
    eng.warmup()
    reqs = synchronized_trace(3, prompt_len=8, max_new=4, seed=5,
                              vocab=cfg.vocab)
    report = eng.serve(reqs)
    per_req = request_core_ledgers(sched, report.records)
    per_core_led = {led.core: led.cm for led in sched.ledgers()}
    for rid, rec in report.records.items():
        assert set(per_req[rid]) == set(per_core_led)
        for c, cm in per_req[rid].items():
            assert cm == per_core_led[c].scaled(rec.vectors)
    # engine-level aggregation: summed over cores == program totals
    agg = eng.core_ledgers(report)
    total = None
    for cm in agg.values():
        total = cm if total is None else total + cm
    assert total == program.mvm_counts().scaled(report.useful_vectors)


class _MeshStub:
    """mesh_placement/device_ledgers read only shape + axis_names; a stub
    lets the placement law be tested for D > device_count in-process."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


def test_mesh_placement_and_device_ledgers():
    spec, cfg, model, params, exe, program, sched = _programmed_setup(
        n_contexts=3)
    mesh = _MeshStub(data=1, model=2)
    place = sched.mesh_placement(mesh, "model")
    assert place == {c: c % 2 for c in range(sched.n_cores)}
    devs = sched.device_ledgers(mesh, "model")
    assert set(devs) <= {0, 1}
    # placement never creates or loses traffic
    from repro.core import isa
    assert isa.total(d.cm for d in devs.values()) == sched.ledger_totals()
    assert (sum(d.comm_bytes for d in devs.values())
            == sum(led.comm_bytes for led in sched.ledgers()))
    # a mesh without the axis collapses onto one slot
    assert set(sched.mesh_placement(_MeshStub(data=1),
                                    "model").values()) == {0}


def test_serve_engine_param_specs_shard_only_aimc_states():
    from jax.sharding import PartitionSpec as P

    from repro.launch.shardings import serve_engine_param_specs

    params = {"blocks": {"wq": jnp.ones((64, 128)) * 0.1,
                         "ln": jnp.ones((64,))}}
    cfg = AimcConfig(tile_rows=128, impl="ref")
    prog = program_model(params, MappingPlan(), cfg)
    installed_shape = jax.eval_shape(lambda: prog.install(params))
    mesh = make_mesh((1, 1), ("data", "model"))
    specs = serve_engine_param_specs(installed_shape, mesh)
    st = specs["blocks"]["wq"]
    assert st.w_q == P(None, None, "model")        # bit lines over model
    assert st.s_w == P(None, "model")
    assert specs["blocks"]["ln"] == P(None)        # digital leaf replicates
    # no model axis on the mesh -> everything replicates
    flat = make_mesh((1,), ("data",))
    specs = serve_engine_param_specs(installed_shape, flat)
    assert specs["blocks"]["wq"].w_q == P(None, None, None)


# ---------------------------------------------------------------------------
# CLI mesh parsing
# ---------------------------------------------------------------------------

def test_parse_mesh_both_syntaxes():
    from repro.launch.serve import parse_mesh
    assert parse_mesh("data:2,model:1") == ((2, 1), ("data", "model"), True)
    assert parse_mesh("model:4") == ((4,), ("model",), True)
    assert parse_mesh("2x1") == ((2, 1), ("data", "model"), False)
    assert parse_mesh("2x4x1") == ((2, 4, 1), ("pod", "data", "model"),
                                   False)
    for bad in ("data:x",                          # malformed size
                "data:2,data:2",                   # duplicate axis
                "data:0",                          # zero-sized axis
                "2",                               # 1 positional size
                "2xa",                             # non-integer positional
                "2x0"):                            # zero positional size
        with pytest.raises(SystemExit):
            parse_mesh(bad)


def test_parse_named_mesh_rejects_positional():
    """The bench/sharded entry points must not let the legacy DxM spelling
    (single-device engine in launch.serve) silently select the sharded
    engine — one spelling, one meaning across CLIs."""
    from repro.launch.serve import force_host_device_count, parse_named_mesh
    assert parse_named_mesh("data:2,model:1") == ((2, 1), ("data", "model"))
    with pytest.raises(SystemExit, match="named"):
        parse_named_mesh("2x1")
    with pytest.raises(SystemExit):
        force_host_device_count("2x1")
    # a unit mesh forces nothing (no XLA_FLAGS mutation needed to test the
    # parse path)
    import os
    before = os.environ.get("XLA_FLAGS")
    assert force_host_device_count("data:1,model:1") == ((1, 1),
                                                         ("data", "model"))
    assert os.environ.get("XLA_FLAGS") == before


@pytest.mark.slow
def test_force_host_device_count_fails_loud_after_late_init():
    """Setting XLA_FLAGS after jax already initialized its backend is a
    silent no-op — the old helper then let a 'data:2' bench run all its
    "sharded" cases on ONE device and report them as a 2-device result.
    The helper must verify the post-init device count and exit nonzero."""
    code = textwrap.dedent("""
        import jax
        assert jax.device_count() == 1, jax.devices()   # backend is up
        from repro.launch.serve import force_host_device_count
        force_host_device_count("data:2,model:1")       # too late: must die
        print("UNREACHABLE")
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        ["src", env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode != 0
    assert "UNREACHABLE" not in proc.stdout
    assert "device" in proc.stderr         # the message names the problem


# ---------------------------------------------------------------------------
# benchmarks.run artifact discipline
# ---------------------------------------------------------------------------

def test_write_report_refuses_partial_overwrite(tmp_path, capsys):
    import json

    from benchmarks.run import write_report
    path = str(tmp_path / "BENCH_all.json")
    # complete run writes (and is stamped complete)
    assert write_report(path, {"summary": {"passed": 1}}, complete=True)
    assert json.load(open(path))["partial"] is False
    # partial run must NOT clobber the existing complete artifact
    assert not write_report(path, {"summary": {"passed": 0}}, complete=False)
    assert json.load(open(path))["summary"]["passed"] == 1
    # partial run with nothing to lose still writes, stamped partial
    fresh = str(tmp_path / "fresh.json")
    assert write_report(fresh, {"summary": {"passed": 0}}, complete=False)
    assert json.load(open(fresh))["partial"] is True
    # ...and a later partial run may refresh a PARTIAL artifact
    assert write_report(fresh, {"summary": {"passed": 2}}, complete=False)
    assert json.load(open(fresh))["summary"]["passed"] == 2
    # a pre-stamp artifact (no "partial" key) is presumed complete
    legacy = str(tmp_path / "legacy.json")
    json.dump({"summary": {}}, open(legacy, "w"))
    assert not write_report(legacy, {"summary": {}}, complete=False)


# ---------------------------------------------------------------------------
# the acceptance bar: forced 2-device host-platform mesh (subprocess —
# XLA's device count is fixed at backend init)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_engine_bit_equal_across_two_devices():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=2 "
            + os.environ.get("XLA_FLAGS", ""))
        import jax, jax.numpy as jnp
        assert jax.device_count() == 2, jax.devices()
        from repro.configs import get_arch
        from repro.core.aimc import AimcConfig
        from repro.core.program import MappingPlan, program_model
        from repro.core.schedule import CoreSchedule
        from repro.launch.mesh import make_mesh
        from repro.models.layers import Execution
        from repro.runtime.batcher import (reconcile, reconcile_cores,
                                           synchronized_trace)
        from repro.runtime.engine import ServeEngine, ShardedServeEngine

        def check(arch, programmed, shape, impl="ref"):
            spec = get_arch(arch); cfg = spec.smoke_cfg
            model = spec.model_module()
            params = model.init(jax.random.PRNGKey(0), cfg)
            prog = sched = None
            if programmed:
                aimc = AimcConfig(impl=impl, input_scale=0.1)
                exe = Execution(mode="aimc", aimc=aimc,
                                compute_dtype="float32", programmed=True)
                prog = program_model(params, MappingPlan(n_contexts=2),
                                     aimc, jax.random.PRNGKey(2))
                params = prog.install(params)
                sched = CoreSchedule.from_program(prog)
            else:
                exe = Execution(compute_dtype="float32")
            mesh = make_mesh(shape, ("data", "model"))
            kw = dict(n_slots=2, prompt_pad=8, max_seq=20,
                      family=spec.family, module=spec.module,
                      cache_dtype=jnp.float32, program=prog, schedule=sched)
            e1 = ServeEngine(model, cfg, exe, params, **kw); e1.warmup()
            e2 = ShardedServeEngine(model, cfg, exe, params, mesh=mesh,
                                    **kw)
            assert e2.warmup() == {"prefill": 1, "insert": 1, "decode": 1}
            reqs = synchronized_trace(4, prompt_len=8, max_new=6, seed=1,
                                      vocab=cfg.vocab)
            r1 = e1.serve(list(reqs)); r2 = e2.serve(list(reqs))
            for r in reqs:
                assert r1.tokens(r.rid) == r2.tokens(r.rid), (
                    arch, shape, r.rid)
            assert e2.compile_counts() == {"prefill": 1, "insert": 1,
                                           "decode": 1}, (arch, shape)
            if prog is not None:
                assert r2.observed_vectors == r2.useful_vectors
                ls, st = reconcile(prog, r2.records, r2.observed_vectors)
                assert ls == st
                cs, stot = reconcile_cores(sched, r2.records,
                                           r2.observed_vectors)
                assert cs == stot
                assert stot == prog.mvm_counts().scaled(r2.observed_vectors)

        check("granite-8b", True, (2, 1))    # slots over data
        check("granite-8b", True, (1, 2))    # crossbar bit lines over model
        # the Pallas kernel cannot be partitioned by GSPMD: the dispatch
        # shard_maps it (kernels/ops.py), bit lines over `model`, rows over
        # `data`
        check("granite-8b", True, (1, 2), impl="pallas_interpret")
        check("granite-8b", True, (2, 1), impl="pallas_interpret")
        check("xlstm-350m", False, (2, 1))   # recurrent state over data

        # read noise under the shard_map: every shard draws its own block
        # of the one-device counter noise
        import numpy as np
        from repro.core.aimc import program_linear, stack_states
        from repro.core.quant import sym_scale
        from repro.kernels import ops
        acfg = AimcConfig(tile_rows=128)
        kx, k1, k2 = jax.random.split(jax.random.PRNGKey(3), 3)
        x = jax.random.normal(kx, (8, 256))
        sts = [program_linear(jax.random.normal(k, (256, 512)) * 0.06, acfg)
               for k in (k1, k2)]
        st2 = stack_states(sts)
        s_x = sym_scale(x).reshape(1, 1)
        f = jax.jit(lambda x: ops.aimc_matmul_v2(
            x, sts[0].w_q, sts[0].s_w, s_x, jnp.uint32(5), adc_step=4.0,
            sigma=2.0, impl="pallas_interpret"))
        g = jax.jit(lambda x: ops.aimc_matmul_stacked(
            x, st2.w_q, st2.s_w, s_x, jnp.uint32(5), adc_step=4.0,
            sigma=2.0, impl="pallas_interpret"))
        want = (np.asarray(f(x)), np.asarray(g(x)))
        for shape in ((1, 2), (2, 1)):
            with jax.set_mesh(make_mesh(shape, ("data", "model"))):
                got = (np.asarray(f(x)), np.asarray(g(x)))
            assert all((a == b).all() for a, b in zip(got, want)), shape
        print("SHARDED_BITEQUAL_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        ["src", env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED_BITEQUAL_OK" in proc.stdout
