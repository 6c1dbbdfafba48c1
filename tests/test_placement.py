"""Auto-placement contract suite (core/placement.py, DESIGN.md §16).

The ISSUE-10 acceptance criteria pinned here:
  * the placer's per-layer packing oracle (`tile.pack_contexts`) agrees
    EXACTLY with the real `ProgramBuilder` packing — same total tiles,
    and the predicted max-per-context is the exact feasibility frontier
    (budget == packmax programs; budget == packmax-1 raises
    `CapacityError`);
  * a returned plan NEVER exceeds the tile budget — per rotation state
    when the model overflows, for the chosen analog set when it fits;
  * more budget never worsens predicted latency (monotone), and the
    chosen split is never worse than all-digital or than the densest
    all-analog prefix that fits;
  * capacity overflow degrades to a time-multiplexed `RotationPlan`
    whose states partition the analog set (nothing silently dropped);
  * the rotating engine serves BIT-EQUAL to the digital static oracle
    while billing one CM_INITIALIZE batch per swap — reconciled per
    event against `AimcProgram.reprogram_counts` — without a single
    post-warmup recompile;
  * per-request CM_* ledgers are refused under rotation (they are
    ill-defined: a request's vectors span states), as are the engine
    combinations that would break bit-stability (prefix cache, chunked
    prefill, health/chaos, sharding, multi-tenant serving).
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.core.aimc import AimcConfig
from repro.core.placement import (PlacementRoofline, RotationPlan,
                                  layer_costs, plan_placement,
                                  reconcile_swaps)
from repro.core.program import CapacityError, MappingPlan, program_model
from repro.core.tile import pack_contexts
from repro.models.layers import Execution
from repro.runtime.batcher import synchronized_trace
from repro.runtime.engine import ServeEngine, static_generate

# the LOCKED placement smoke config (ci.sh --fast serves the same one):
# small tiles force the smoke model to overflow a 2-tile budget, and the
# aimc output at this precision is token-equal to digital on this trace
ACFG = AimcConfig(impl="ref", adc_alpha=0.5, tile_rows=64)
SEED = 1


@pytest.fixture(scope="module")
def tfm():
    spec = get_arch("granite-8b")
    cfg = spec.smoke_cfg
    model = spec.model_module()
    params = model.init(jax.random.PRNGKey(SEED), cfg)
    return spec, cfg, model, params


@pytest.fixture(scope="module")
def placed(tfm):
    """Uncapped placement on the smoke model (every candidate fits)."""
    _, _, _, params = tfm
    return plan_placement(params, MappingPlan(), ACFG,
                          tiles_per_context=None, n_contexts=1)


def _packmax_of(res, resident, budgetless_cfg=ACFG):
    """Independent re-packing of ``resident`` via the public oracle."""
    items = [c.item for c in res.costs if c.path in set(resident)]
    per = pack_contexts(items, res.n_contexts, budgetless_cfg.tile_rows,
                        budgetless_cfg.tile_cols)
    return max(per) if per else 0


# ---------------------------------------------------------------------------
# cost enumeration + packing oracle vs the real program builder
# ---------------------------------------------------------------------------

def test_layer_costs_cover_every_mapped_leaf(tfm, placed):
    _, _, _, params = tfm
    prog = program_model(params, MappingPlan(), ACFG, jax.random.PRNGKey(1))
    assert {c.path for c in placed.costs} == set(prog.names)
    for c in placed.costs:
        assert c.t_digital > 0.0 and c.t_analog > 0.0
        assert c.tiles_alone >= 1 and c.instances >= 1
    # analog/digital is a partition of the cost set
    assert set(placed.analog) | set(placed.digital) == set(prog.names)
    assert not set(placed.analog) & set(placed.digital)


def test_pack_contexts_is_the_program_builders_packing(tfm, placed):
    _, _, _, params = tfm
    per = pack_contexts([c.item for c in placed.costs], 1,
                        ACFG.tile_rows, ACFG.tile_cols)
    prog = program_model(params, MappingPlan(), ACFG, jax.random.PRNGKey(1))
    assert prog.n_tiles == sum(per)
    # the predicted packmax is the exact capacity frontier of the builder
    packmax = max(per)
    ok = MappingPlan(tiles_per_context=packmax)
    program_model(params, ok, ACFG, jax.random.PRNGKey(1))   # must fit
    with pytest.raises(CapacityError):
        program_model(params, MappingPlan(tiles_per_context=packmax - 1),
                      ACFG, jax.random.PRNGKey(1))


def test_layer_costs_standalone_matches_plan_scope(tfm, placed):
    _, _, _, params = tfm
    costs = layer_costs(params, MappingPlan(), ACFG)
    assert [c.path for c in costs] == [c.path for c in placed.costs]
    assert [c.t_analog for c in costs] == [c.t_analog for c in placed.costs]


# ---------------------------------------------------------------------------
# budget law: cap honored, monotone, dominates the trivial splits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("budget", [1, 2, 3, 4, 8])
def test_budget_never_exceeded(tfm, budget):
    _, _, _, params = tfm
    res = plan_placement(params, MappingPlan(), ACFG,
                         tiles_per_context=budget, n_contexts=1)
    if res.overflow:
        assert res.rotation is not None
        for state_names in res.rotation.states():
            assert _packmax_of(res, state_names) <= budget, \
                f"rotation state {state_names} busts budget {budget}"
    else:
        assert res.rotation is None
        assert _packmax_of(res, res.analog) <= budget


def test_more_budget_never_worse(tfm, placed):
    _, _, _, params = tfm
    pred = [plan_placement(params, MappingPlan(), ACFG, tiles_per_context=b,
                           n_contexts=1).predicted_s
            for b in (1, 2, 3, 4, 6, 8)]
    assert all(a >= b - 1e-15 for a, b in zip(pred, pred[1:]))
    # the uncapped result is the floor of the whole sweep
    assert all(p >= placed.predicted_s - 1e-15 for p in pred)


def test_chosen_split_dominates_trivial_splits(tfm):
    _, _, _, params = tfm
    for b in (1, 2, 4, None):
        res = plan_placement(params, MappingPlan(), ACFG,
                             tiles_per_context=b, n_contexts=1)
        assert res.predicted_s <= res.predicted_digital_s + 1e-15
        assert res.predicted_s <= res.predicted_analog_fit_s + 1e-15
        # the prediction helper agrees with the headline numbers
        assert res.predicted_for(()) == pytest.approx(
            res.predicted_digital_s)


# ---------------------------------------------------------------------------
# overflow -> rotation plan invariants
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def overflowed(tfm):
    _, _, _, params = tfm
    res = plan_placement(params, MappingPlan(), ACFG,
                         tiles_per_context=2, n_contexts=1)
    assert res.overflow and res.rotation is not None
    return res


def test_rotation_partitions_the_analog_set(overflowed):
    rot = overflowed.rotation
    assert rot.n_states >= 2
    rotated = [n for g in rot.groups for n in g]
    # hot + rotating groups partition all_names: disjoint, nothing dropped
    assert sorted(rotated) == sorted(set(rotated))
    assert not set(rot.hot) & set(rotated)
    assert set(rot.all_names) == set(rot.hot) | set(rotated)
    # the rotation covers every positive-savings candidate: the resident
    # prefix (`analog`) plus each dropped layer either rotates in or is
    # permanently digital because it cannot fit even alone — nothing is
    # silently dropped
    assert set(overflowed.analog) <= set(rot.all_names)
    pos = {c.path for c in overflowed.costs if c.t_digital > c.t_analog}
    assert set(rot.all_names) | set(rot.digital) == pos
    assert not set(rot.digital) & set(rot.all_names)
    # incoming() cycles over the groups
    for s in range(2 * rot.n_states):
        assert rot.incoming(s) == rot.groups[s % len(rot.groups)]


def test_rotation_plan_programs_uncapped(tfm, overflowed):
    _, _, _, params = tfm
    rot = overflowed.rotation
    plan = rot.plan()
    assert plan.tiles_per_context is None       # one program, all states
    prog = program_model(params, plan, ACFG, jax.random.PRNGKey(1))
    assert set(prog.names) == set(rot.all_names)


def test_singleton_budget_rotates_everything(tfm):
    _, _, _, params = tfm
    res = plan_placement(params, MappingPlan(), ACFG,
                         tiles_per_context=1, n_contexts=1)
    assert res.overflow
    rot = res.rotation
    # nothing fits permanently at budget 1 on this model: all groups are
    # singletons and the hot set is empty
    assert rot.hot == ()
    assert all(len(g) == 1 for g in rot.groups)
    assert rot.n_states == len(rot.all_names)
    assert set(res.analog) <= set(rot.all_names)


def test_rotation_plan_validation():
    with pytest.raises(ValueError):
        RotationPlan(hot=(), groups=(("a",),), digital=(), n_contexts=1,
                     tiles_per_context=1, swap_every=0)


# ---------------------------------------------------------------------------
# the rotating engine: bit-equality, swap billing, compile stability
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rotating(tfm, overflowed):
    """Rotation engine served on the LOCKED smoke trace."""
    spec, cfg, model, params = tfm
    rot = overflowed.rotation
    prog = program_model(params, rot.plan(), ACFG,
                         jax.random.PRNGKey(SEED + 2))
    rparams = tuple(prog.install_subset(params, ns) for ns in rot.states())
    exe = Execution(mode="aimc", aimc=ACFG, compute_dtype="float32",
                    programmed=True)
    eng = ServeEngine(model, cfg, exe, rparams[0], n_slots=4, prompt_pad=8,
                      max_seq=14, family=spec.family, module=spec.module,
                      program=prog, rotation=rot, rotation_params=rparams)
    counts = eng.warmup()
    reqs = synchronized_trace(4, prompt_len=8, max_new=6, seed=SEED,
                              vocab=cfg.vocab)
    report = eng.serve(reqs)
    return prog, exe, eng, reqs, report, counts


def test_rotating_engine_bit_equal_to_digital_oracle(tfm, rotating):
    _, cfg, model, params = tfm
    _, exe, _, reqs, report, _ = rotating
    prompts = jnp.asarray([r.prompt for r in reqs], jnp.int32)
    dig = dataclasses.replace(exe, mode="digital")
    gen, _ = static_generate(model, cfg, dig, params, prompts, 6,
                             max_seq=14, cache_dtype=jnp.float32)
    for r in reqs:
        assert report.tokens(r.rid) == [int(t) for t in gen[r.rid]], \
            f"req {r.rid} diverged from the digital static oracle"


def test_swap_billing_reconciles_per_event(rotating):
    prog, _, _, _, report, _ = rotating
    assert report.n_swaps > 0
    assert len(report.swap_events) == report.n_swaps
    for ev in report.swap_events:
        assert ev.initialize == prog.reprogram_counts(ev.incoming).initialize
        assert ev.initialize > 0
    assert report.swap_initialize == sum(
        ev.initialize for ev in report.swap_events)
    assert reconcile_swaps(prog, report)
    assert report.wall_swap_s >= 0.0
    # swap chunks are non-decreasing and states advance cyclically
    chunks = [ev.chunk for ev in report.swap_events]
    assert chunks == sorted(chunks)


def test_rotation_never_recompiles_after_warmup(rotating):
    _, _, eng, _, _, counts = rotating
    # one prefill + one decode closure PER rotation state (distinct
    # treedefs), one shared insert
    assert counts == {"prefill": 2, "insert": 1, "decode": 2}
    assert eng.compile_counts() == counts, \
        "rotation swap recompiled an engine closure after warmup"


def test_ledgers_refused_under_rotation(rotating):
    _, _, eng, _, report, _ = rotating
    with pytest.raises(ValueError, match="rotation"):
        eng.ledgers(report)


# ---------------------------------------------------------------------------
# invalid combinations are rejected at construction time
# ---------------------------------------------------------------------------

def _mk(tfm, **kw):
    spec, cfg, model, params = tfm
    kw.setdefault("n_slots", 2)
    kw.setdefault("prompt_pad", 8)
    kw.setdefault("max_seq", 14)
    kw.setdefault("family", spec.family)
    kw.setdefault("module", spec.module)
    return ServeEngine(model, cfg, kw.pop("exe", Execution(
        compute_dtype="float32")), kw.pop("params", params), **kw)


def test_rotation_requires_program_and_matching_params(tfm, overflowed):
    rot = overflowed.rotation
    with pytest.raises(ValueError, match="AimcProgram"):
        _mk(tfm, rotation=rot, rotation_params=(None,) * rot.n_states)


def test_rotation_params_must_match_states(tfm, rotating, overflowed):
    prog, exe, _, _, _, _ = rotating
    rot = overflowed.rotation
    with pytest.raises(ValueError, match="state"):
        _mk(tfm, exe=exe, program=prog, rotation=rot,
            rotation_params=(None,))


@pytest.mark.parametrize("kw", [dict(prefix_cache=True, page_size=4,
                                     n_pages=16),
                                dict(prefill_chunk=4, page_size=4,
                                     n_pages=16)])
def test_rotation_rejects_cached_prefill(tfm, rotating, overflowed, kw):
    prog, exe, _, _, _, _ = rotating
    rot = overflowed.rotation
    rparams = (None,) * rot.n_states
    with pytest.raises(ValueError):
        _mk(tfm, exe=exe, program=prog, rotation=rot,
            rotation_params=rparams, **kw)


def test_sharded_engine_rejects_rotation(tfm, overflowed):
    from repro.runtime.engine import ShardedServeEngine
    spec, cfg, model, params = tfm
    with pytest.raises(ValueError, match="rotation"):
        ShardedServeEngine(model, cfg, Execution(compute_dtype="float32"),
                           params, mesh=None, rotation=overflowed.rotation)


def test_model_server_rejects_rotation_engine(rotating):
    from repro.runtime.server import ModelServer
    from repro.runtime.tenancy import TenantPolicy
    _, _, eng, _, _, _ = rotating
    with pytest.raises(ValueError, match="rotation"):
        ModelServer({"m": eng}, [TenantPolicy("t", "m")])


# ---------------------------------------------------------------------------
# predicted-vs-measured roofline helper
# ---------------------------------------------------------------------------

def test_roofline_fit_recovers_affine_law():
    modeled = [1e-6, 2e-6, 5e-6, 1e-5]
    measured = [3e-6 + 2.0 * t for t in modeled]
    fit = PlacementRoofline.fit(modeled, measured)
    assert fit.t_fixed_s == pytest.approx(3e-6, rel=1e-6)
    assert fit.scale == pytest.approx(2.0, rel=1e-6)
    assert max(fit.residuals(modeled, measured)) < 1e-9
    with pytest.raises(ValueError):
        PlacementRoofline.fit([1e-6], [1e-6])
