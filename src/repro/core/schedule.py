"""Multi-core AIMC scheduler — the executable twin of the cost model's phases.

The paper's headline results come from *multi-core* mappings: the MLP/LSTM
explorations column-split layers across cores with mutex hand-offs between
phases (§VII-D, §VIII-D), and the CNN pipelines one conv layer per core at
position granularity (§IX-A). `core.workloads` describes those mappings
analytically; this module makes them RUN:

  * ``Shard``          — one (slice of a) programmed matrix assigned to one
    virtual core in one phase, with its dataflow edges (comm/load/store
    bytes) declared statically.
  * ``select_columns`` — exact column-split of an `AimcLinearState`. ADC
    quantization, per-column scales and row-block accumulation are all
    column-independent, so the concatenated shard outputs are bit-identical
    to the single-core apply (noise off) — the property every multi-core
    mapping in the paper relies on.
  * ``CoreSchedule``   — lowers an `AimcProgram` onto N virtual cores.
    ``apply(name, x)`` executes a matrix across all its shards (interleaved
    on one device); ``apply_sharded`` runs one shard per mesh device via
    `shard_map`. ``ledgers()`` emits per-core CM_*/comm-byte accounts, and
    ``modeled_latency()`` prices them through the SAME
    `costmodel.aimc_mvm_time` the analytical model uses — measured
    (executable) and predicted (analytical) views can be compared case by
    case (`benchmarks/bench_pipeline.py`).
  * dataflow laws      — ``sequential_latency`` (per-inference time = sum
    over phases of the slowest core, the MLP/LSTM mutex chain) and
    ``pipelined_latency`` (= slowest stage, the CNN position pipeline),
    mirroring `costmodel.evaluate`'s treatment of `Workload.pipelined`.
  * ``OverlapRoofline`` — the serving-loop latency law: T_step(k) =
    t_step_s + t_round_s/k, fitted from measured chunked-decode step
    times; predicts (and the serving bench gates) the host-overlap gain
    of the k-step scanned decode loop (DESIGN.md §13).

Builders for every paper multi-core case live at the bottom
(`mlp_schedule`, `lstm_schedule`, `cnn_schedule`) and `from_program` lowers
any `program_model` output (zoo models) using its MappingPlan contexts as
cores. `mesh_placement` / `device_ledgers` fold the virtual cores onto a
JAX mesh's model-axis devices for the sharded serving engine
(DESIGN.md §11) — placement regroups the books but never creates or loses
traffic.

Invariants (pinned by tests/test_schedule.py): column splits are EXACT
(concatenated shard outputs == single-core apply, noise off); unsplit
per-core ledgers sum to `program.mvm_counts()` while column splits
partition dequeue/initialize exactly and duplicate queue/process by the
split factor; `modeled_latency()` equals `costmodel.evaluate()` on the
matching Workload IR bit-for-bit (shared accounting).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isa
from repro.core.aimc import AimcLinearState, aimc_apply
from repro.core.costmodel import (CALIB, HIGH_POWER, aimc_mvm_time,
                                  fused_epilogue_time)
from repro.core.program import AimcProgram


# ---------------------------------------------------------------------------
# Exact column splitting
# ---------------------------------------------------------------------------

def select_columns(state: AimcLinearState,
                   ranges: Sequence[tuple[int, int]]) -> AimcLinearState:
    """A new programmed state holding only the given logical column ranges.

    The slice is EXACT: per-column weight scales, ADC codes and row-block
    accumulation never mix columns, so (noise off)

        aimc_apply(select_columns(st, R), x) == aimc_apply(st, x)[..., idx(R)]

    bit for bit. Non-contiguous ranges are allowed (the LSTM case-4 gate
    slices pick one stripe out of each of the four gate blocks)."""
    for a, b in ranges:
        if not (0 <= a < b <= state.n):
            raise ValueError(f"column range [{a}, {b}) outside n={state.n}")
    idx = np.concatenate([np.arange(a, b) for a, b in ranges])
    if len(np.unique(idx)) != idx.size:
        raise ValueError("overlapping column ranges")
    n_new = int(idx.size)
    np_new = -(-n_new // 128) * 128          # keep TPU lane alignment
    w_q = jnp.asarray(state.w_q)[..., idx]
    s_w = jnp.asarray(state.s_w)[..., idx]
    pad = np_new - n_new
    if pad:
        w_q = jnp.pad(w_q, [(0, 0)] * (w_q.ndim - 1) + [(0, pad)])
        s_w = jnp.pad(s_w, [(0, 0)] * (s_w.ndim - 1) + [(0, pad)])
    return AimcLinearState(w_q=w_q, s_w=s_w, k=state.k, n=n_new)


# ---------------------------------------------------------------------------
# Shards and per-core ledgers
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shard:
    """One (slice of a) programmed matrix on one virtual core.

    ``cols=None`` assigns the whole matrix; otherwise a tuple of logical
    [start, stop) column ranges. ``count`` is the number of MVMs this shard
    fires per inference (conv output positions re-using the kernel).
    ``comm_in_bytes``/``comm_events`` are the activation bytes and mutex
    hand-offs this core pays before computing (paper: sequential cross-core
    dependency chain); ``comm_out_bytes`` what it forwards.
    ``digital_cycles`` prices the stage's CPU-side element-wise tail (relu /
    cell math / softmax ...) in core cycles, so schedule-modeled latency is
    comparable to `costmodel.evaluate` on the matching `Workload`.
    ``epilogue_fn``/``epilogue_elems`` instead declare an activation FUSED
    into the shard's dequeue loop (kernel v2's fused epilogue), priced by
    the shared `costmodel.fused_epilogue_time` — cheap epilogues hide under
    the per-word transaction latency and cost nothing. ``epilogue_elems``
    is PER FIRING (scaled by count * instances like the MVM itself)."""

    name: str
    core: int
    phase: int
    cols: tuple[tuple[int, int], ...] | None = None
    count: int = 1
    comm_in_bytes: int = 0
    comm_out_bytes: int = 0
    comm_events: int = 0
    load_bytes: int = 0
    store_bytes: int = 0
    digital_cycles: float = 0.0
    epilogue_fn: str = ""
    epilogue_elems: int = 0

    def n_cols(self, state: AimcLinearState) -> int:
        if self.cols is None:
            return state.n
        return sum(b - a for a, b in self.cols)


@dataclasses.dataclass(frozen=True)
class CoreLedger:
    """Static per-core account of one inference — the same units the cost
    model prices (`isa.CmCounts` + comm/load/store bytes)."""

    core: int
    cm: isa.CmCounts
    comm_bytes: int = 0
    comm_events: int = 0
    load_bytes: int = 0
    store_bytes: int = 0

    def row(self) -> list:
        return [self.core, self.cm.queue, self.cm.process, self.cm.dequeue,
                self.comm_bytes, self.load_bytes + self.store_bytes]


# ---------------------------------------------------------------------------
# Dataflow latency laws (mirrors costmodel.evaluate's Workload.pipelined)
# ---------------------------------------------------------------------------

def sequential_latency(phase_times: Sequence[Sequence[float]]) -> float:
    """Mutex hand-off semantics (MLP/LSTM): stages inside a phase run in
    parallel on different cores, phases chain — per-inference latency is the
    sum over phases of the slowest stage in each."""
    return sum(max(ph) if len(ph) else 0.0 for ph in phase_times)


def pipelined_latency(phase_times: Sequence[Sequence[float]]) -> float:
    """Position-level pipelining (CNN): at steady state every stage works on
    a different inference — per-inference latency is the slowest stage."""
    return max((t for ph in phase_times for t in ph), default=0.0)


@dataclasses.dataclass(frozen=True)
class OverlapRoofline:
    """Calibrated host-overlap roofline for the chunked decode loop.

    The serving engine's per-token cost splits into two empirical
    constants (the SNIPPETS.md discipline: fit measured constants, then
    gate predicted-vs-measured like bench_pipeline's ratio checks):

        T_step(k) = t_step_s + t_round_s / k

    ``t_step_s`` is the irreducible per-step device time (model math plus,
    on a mesh, the model-axis reduction — it scales with neither k nor the
    host), and ``t_round_s`` is the per-HOST-ROUND overhead (dispatch,
    sync, readback, Python bookkeeping) that a k-step `lax.scan` chunk
    amortizes over k steps. `fit` recovers both by least squares from
    measured synchronous per-step times at >= 2 chunk sizes; `predict_
    step_s` / `speedup` then EXPLAIN the measured chunked-decode gain, and
    the serving bench gates |predicted - measured| (BENCH_serving.json).
    """
    t_step_s: float
    t_round_s: float

    @classmethod
    def fit(cls, step_times: dict[int, float]) -> "OverlapRoofline":
        """Least-squares fit of (t_step_s, t_round_s) over the basis
        [1, 1/k]. ``step_times``: chunk size k -> measured mean seconds
        per decode STEP (chunk wall / k) at that k. Needs >= 2 distinct
        chunk sizes; negative fitted constants clamp to 0 (wall-clock
        noise can tilt the regression, but time is not refundable)."""
        ks = sorted(step_times)
        if len(ks) < 2:
            raise ValueError(
                f"OverlapRoofline.fit needs step times at >= 2 chunk "
                f"sizes, got {ks}")
        a_mat = np.array([[1.0, 1.0 / k] for k in ks])
        y = np.array([step_times[k] for k in ks])
        (t_step, t_round), *_ = np.linalg.lstsq(a_mat, y, rcond=None)
        return cls(t_step_s=max(float(t_step), 0.0),
                   t_round_s=max(float(t_round), 0.0))

    def predict_step_s(self, k: int) -> float:
        """Predicted seconds per decode step at chunk size ``k``."""
        if k < 1:
            raise ValueError(f"chunk size must be >= 1, got {k}")
        return self.t_step_s + self.t_round_s / k

    def speedup(self, k_from: int = 1, k_to: int = 8) -> float:
        """Predicted step-time ratio T(k_from) / T(k_to) — the overlap
        gain the chunked loop should realize by moving from k_from to
        k_to host-round amortization."""
        return self.predict_step_s(k_from) / self.predict_step_s(k_to)

    def residuals(self, step_times: dict[int, float]) -> dict[int, float]:
        """k -> relative |predicted - measured| / measured, the
        calibration quality the bench gates on."""
        return {k: abs(self.predict_step_s(k) - t) / t
                for k, t in step_times.items()}


# ---------------------------------------------------------------------------
# CoreSchedule
# ---------------------------------------------------------------------------

class CoreSchedule:
    """An `AimcProgram` lowered onto N virtual cores.

    Built once at setup time (plain Python over static shapes — never inside
    jit); ``apply`` is jit-friendly and numerically equal to the single-core
    programmed path (noise off)."""

    def __init__(self, program: AimcProgram, shards: Sequence[Shard],
                 pipelined: bool = False, name: str = ""):
        self.program = program
        self.cfg = program.cfg
        self.shards = tuple(shards)
        self.pipelined = pipelined
        self.name = name
        if not self.shards:
            raise ValueError("a schedule needs at least one shard")

        self._by_name: dict[str, tuple[Shard, ...]] = {}
        for sh in self.shards:
            if sh.name not in program:
                raise KeyError(f"shard references unmapped matrix {sh.name!r}")
            self._by_name.setdefault(sh.name, ())
            self._by_name[sh.name] += (sh,)

        # pre-slice states + record the inverse column permutation per matrix
        self._states: dict[tuple[str, int], AimcLinearState] = {}
        self._inv_perm: dict[str, np.ndarray | None] = {}
        for mname, shs in self._by_name.items():
            st = program[mname]
            if len(shs) == 1 and shs[0].cols is None:
                self._inv_perm[mname] = None
                continue
            if any(sh.cols is None for sh in shs):
                raise ValueError(
                    f"matrix {mname!r}: mixing full and column-split shards")
            idx = np.concatenate(
                [np.concatenate([np.arange(a, b) for a, b in sh.cols])
                 for sh in shs])
            if not np.array_equal(np.sort(idx), np.arange(st.n)):
                raise ValueError(
                    f"matrix {mname!r}: shard columns are not a disjoint "
                    f"cover of 0..{st.n}")
            for i, sh in enumerate(shs):
                self._states[(mname, i)] = select_columns(st, sh.cols)
            self._inv_perm[mname] = np.argsort(idx)

    # -- shape stats ---------------------------------------------------------
    @property
    def n_cores(self) -> int:
        return max(sh.core for sh in self.shards) + 1

    @property
    def n_phases(self) -> int:
        return max(sh.phase for sh in self.shards) + 1

    def shards_of(self, name: str) -> tuple[Shard, ...]:
        return self._by_name[name]

    # -- execution: interleaved on one device --------------------------------
    def apply(self, name: str, x: jnp.ndarray,
              key: jax.Array | None = None) -> jnp.ndarray:
        """Run matrix `name` across all its shards and reassemble the full
        output — the executable form of the column-split mapping. With one
        full shard this IS the single-core path. Noise draws (when enabled)
        are per shard, so multi-core noise differs from single-core by
        design — each core owns physically distinct crossbar columns."""
        shs = self._by_name[name]
        if self._inv_perm[name] is None:
            return aimc_apply(self.program[name], x, self.cfg, key)
        parts = []
        for i in range(len(shs)):
            sub_key = jax.random.fold_in(key, i) if key is not None else None
            parts.append(aimc_apply(self._states[(name, i)], x, self.cfg,
                                    sub_key))
        y = jnp.concatenate(parts, axis=-1)
        return y[..., self._inv_perm[name]]

    # -- execution: one core per mesh device via shard_map --------------------
    def apply_sharded(self, name: str, x: jnp.ndarray, mesh,
                      axis: str = "model") -> jnp.ndarray:
        """`apply`, but with the per-core column shards distributed along a
        mesh axis: each device holds (a group of) cores' conductance codes
        and computes only its slice; slices concatenate on the way out. The
        input is replicated — every core queues the full activation vector,
        exactly the paper's case-4 dataflow."""
        from jax.sharding import PartitionSpec as P

        shs = self._by_name[name]
        if self._inv_perm[name] is None:
            raise ValueError(f"matrix {name!r} has a single full shard; "
                             "use apply() (nothing to distribute)")
        states = [self._states[(name, i)] for i in range(len(shs))]
        k, n = states[0].k, states[0].n
        if any(st.n != n or st.w_q.shape != states[0].w_q.shape
               for st in states):
            raise ValueError("apply_sharded needs equal-size column shards")
        n_dev = mesh.shape[axis]
        if len(states) % n_dev:
            raise ValueError(f"{len(states)} shards not divisible over "
                             f"{n_dev} devices on axis {axis!r}")
        w_q = jnp.stack([st.w_q for st in states])
        s_w = jnp.stack([st.s_w for st in states])
        cfg = self.cfg

        def shard_fn(wq_l, sw_l, x_l):
            def one(wq_i, sw_i):
                st = AimcLinearState(w_q=wq_i, s_w=sw_i, k=k, n=n)
                return aimc_apply(st, x_l, cfg)

            return jax.vmap(one)(wq_l, sw_l)

        parts = jax.shard_map(shard_fn, mesh=mesh,
                              in_specs=(P(axis), P(axis), P()),
                              out_specs=P(axis), check_vma=False)(w_q, s_w, x)
        y = jnp.concatenate(list(parts), axis=-1)
        return y[..., self._inv_perm[name]]

    # -- static accounting (the cost model's units) ---------------------------
    def ledgers(self) -> tuple[CoreLedger, ...]:
        """Per-core CM_*/comm-byte accounts for ONE inference.

        Column-split cores each queue the FULL input vector (the paper's
        case-4 semantics: every core feeds its private tile), so summed
        queue/process counts exceed the single-core program's by the split
        factor while dequeue/initialize partition exactly — `ledger_totals`
        vs `program.mvm_counts()` quantifies the multi-core queue tax."""
        acc = {c: [isa.CmCounts(), 0, 0, 0, 0] for c in range(self.n_cores)}
        for sh in self.shards:
            st = self.program[sh.name]
            cm = isa.mvm_counts(st.k, sh.n_cols(st), self.cfg.tile_rows)
            a = acc[sh.core]
            a[0] = a[0] + cm.scaled(sh.count * st.instances)
            a[1] += sh.comm_in_bytes + sh.comm_out_bytes
            a[2] += sh.comm_events
            a[3] += sh.load_bytes
            a[4] += sh.store_bytes
        return tuple(CoreLedger(c, *acc[c]) for c in sorted(acc))

    def ledger_totals(self) -> isa.CmCounts:
        return isa.total(led.cm for led in self.ledgers())

    # -- predicted latency through the shared cost-model accounting -----------
    def shard_time(self, sh: Shard, sys=HIGH_POWER, p=CALIB,
                   coupling: str = "tight") -> float:
        """Modeled busy time of one shard — CM_* traffic priced by
        `costmodel.aimc_mvm_time` (the same function `evaluate()` uses) plus
        its comm/load/store edges."""
        st = self.program[sh.name]
        cm = isa.mvm_counts(st.k, sh.n_cols(st), self.cfg.tile_rows)
        t_q, t_p, t_d = aimc_mvm_time(cm, sys, p, coupling)
        reps = sh.count * st.instances
        t = (t_q + t_p + t_d) * reps
        if sh.epilogue_fn:
            # epilogue_elems is per firing; elems and the hiding dequeue
            # budget scale together (mirrors costmodel._stage_time's
            # op.count scaling)
            t += fused_epilogue_time(
                sh.epilogue_elems * reps, sh.epilogue_fn,
                cm.dequeue * reps, sys, p)
        f = sys.freq_hz
        t += sh.comm_events * p.sync_s
        t += (sh.comm_in_bytes + sh.comm_out_bytes) * p.comm_cycles_per_byte / f
        t += sh.load_bytes * p.load_cycles_per_byte / f
        t += sh.store_bytes * p.store_cycles_per_byte / f
        t += sh.digital_cycles / f
        return t

    def phase_times(self, sys=HIGH_POWER, p=CALIB,
                    coupling: str = "tight") -> tuple[tuple[float, ...], ...]:
        """Per phase, the modeled busy time of each active core."""
        per: dict[tuple[int, int], float] = {}
        for sh in self.shards:
            key = (sh.phase, sh.core)
            per[key] = per.get(key, 0.0) + self.shard_time(sh, sys, p, coupling)
        out = []
        for ph in range(self.n_phases):
            out.append(tuple(t for (p_, _c), t in sorted(per.items())
                             if p_ == ph))
        return tuple(out)

    def modeled_latency(self, sys=HIGH_POWER, p=CALIB,
                        coupling: str = "tight") -> float:
        """Per-inference latency under this schedule's dataflow law."""
        times = self.phase_times(sys, p, coupling)
        law = pipelined_latency if self.pipelined else sequential_latency
        return law(times)

    # -- mesh placement (sharded serving, DESIGN.md §11) -----------------------
    def mesh_placement(self, mesh, axis: str = "model",
                       dead: Sequence[int] = ()) -> dict[int, int]:
        """virtual core -> device slot along mesh ``axis`` (round-robin).

        The placement rule the sharded serving engine uses: cores fold onto
        the model-parallel devices in index order, so an N-core schedule on
        a D-device axis puts core c on device ``c % D``. With D >= N every
        core owns a device (the paper's one-core-per-unit regime); with
        D < N devices time-share cores exactly as a single device
        time-shares every core today — the ledgers are placement-invariant
        either way. A mesh without ``axis`` is a single device slot.

        ``dead`` lists device slots lost mid-serve (the chaos/fault path):
        cores fold round-robin over the SURVIVING slots only, preserving
        round-robin order — the drain-and-remap rule `runtime.health` pairs
        with tile reprogramming. Killing every slot raises."""
        n_dev = mesh.shape[axis] if axis in mesh.axis_names else 1
        alive = [d for d in range(n_dev) if d not in set(dead)]
        if not alive:
            raise ValueError(f"mesh_placement: all {n_dev} device slot(s) "
                             f"on axis {axis!r} are dead")
        return {c: alive[c % len(alive)] for c in range(self.n_cores)}

    def device_ledgers(self, mesh, axis: str = "model",
                       dead: Sequence[int] = ()) -> dict[int, CoreLedger]:
        """device slot -> per-inference ledger summed over the cores placed
        there (`mesh_placement`). The ``core`` field of each returned
        `CoreLedger` is the DEVICE slot; summed over devices the books equal
        `ledger_totals()` — placement never creates or loses traffic (with
        or without ``dead`` slots excluded)."""
        place = self.mesh_placement(mesh, axis, dead=dead)
        acc: dict[int, list] = {}
        for led in self.ledgers():
            d = place[led.core]
            if d not in acc:
                acc[d] = [isa.CmCounts(), 0, 0, 0, 0]
            a = acc[d]
            a[0] = a[0] + led.cm
            a[1] += led.comm_bytes
            a[2] += led.comm_events
            a[3] += led.load_bytes
            a[4] += led.store_bytes
        return {d: CoreLedger(d, *acc[d]) for d in sorted(acc)}

    def summary(self) -> str:
        law = "pipelined" if self.pipelined else "sequential"
        return (f"CoreSchedule[{self.name or 'anon'}]: {len(self.shards)} "
                f"shards of {len(self._by_name)} matrices on "
                f"{self.n_cores} core(s), {self.n_phases} phase(s), {law}; "
                f"modeled {self.modeled_latency() * 1e6:.1f}us/inf")

    def __repr__(self) -> str:
        return f"<{self.summary()}>"

    # -- lowering a whole-model program ---------------------------------------
    @classmethod
    def from_program(cls, program: AimcProgram,
                     pipelined: bool = False) -> "CoreSchedule":
        """Lower a `program_model` output onto its MappingPlan contexts: each
        context is a virtual core, each mapped matrix a phase in registry
        order, with an int8 activation hand-off (k bytes + one mutex) charged
        whenever consecutive matrices sit on different cores."""
        shards = []
        prev_core = None
        for i, name in enumerate(program.names):
            st = program[name]
            core = program.contexts[i]
            hand_off = prev_core is not None and core != prev_core
            shards.append(Shard(
                name=name, core=core, phase=i,
                comm_in_bytes=st.k if hand_off else 0,
                comm_events=1 if hand_off else 0))
            prev_core = core
        return cls(program, shards, pipelined=pipelined, name="from_program")


# ---------------------------------------------------------------------------
# Pipelined stream execution (position-level pipelining, measured view)
# ---------------------------------------------------------------------------

def pipeline_run(stage_fns: Sequence[Callable], inputs: Sequence):
    """Push a stream of inputs through chained stages, measuring per-stage
    wallclock. Pipelining changes TIMING, not values — outputs are identical
    to sequential execution; the per-stage times feed the two latency laws
    (measured pipelined latency ~ max stage, sequential ~ sum)."""
    times = [0.0] * len(stage_fns)
    outs = []
    for x in inputs:
        for i, fn in enumerate(stage_fns):
            t0 = time.perf_counter()
            x = fn(x)
            jax.block_until_ready(x)
            times[i] += time.perf_counter() - t0
        outs.append(x)
    n = max(len(inputs), 1)
    return outs, tuple(t / n for t in times)


# ---------------------------------------------------------------------------
# Paper-case schedule builders (workloads.py's analytical twins, executable)
# ---------------------------------------------------------------------------

def mlp_schedule(program: AimcProgram, cores: int = 1,
                 p=CALIB, fuse_epilogue: bool = False) -> CoreSchedule:
    """The paper's MLP analog mappings (Fig. 6) over entries fc1/fc2.

    cores=1 -> case 1 (both layers one core); cores=2 -> case 3 (layer per
    core, mutex hand-off); cores=4 -> case 4 (each layer column-split over
    two cores, all-to-all half hand-offs). Comm edges and digital relu
    cycles mirror `workloads.mlp_workloads` op for op, so
    `modeled_latency()` tracks `costmodel.evaluate` on the same case.
    ``fuse_epilogue`` folds each layer's relu into its dequeue loop (kernel
    v2) instead of a separate digital pass — the matching workloads carry
    `Op(..., epilogue="relu")`."""
    n_in, n1 = program["fc1"].k, program["fc1"].n
    n2 = program["fc2"].n
    relu = p.elem_cycles["relu"]

    def tail(elems):
        """Per-shard relu epilogue: fused into the dequeue or digital."""
        if fuse_epilogue:
            return {"epilogue_fn": "relu", "epilogue_elems": elems}
        return {"digital_cycles": elems * relu}

    if cores == 1:
        shards = [Shard("fc1", 0, 0, load_bytes=n_in, **tail(n1)),
                  Shard("fc2", 0, 1, store_bytes=n2, **tail(n2))]
    elif cores == 2:
        shards = [Shard("fc1", 0, 0, load_bytes=n_in, **tail(n1)),
                  Shard("fc2", 1, 1, comm_in_bytes=n1, comm_events=1,
                        store_bytes=n2, **tail(n2))]
    elif cores == 4:
        h1, h2 = n1 // 2, n2 // 2
        shards = [
            Shard("fc1", 0, 0, cols=((0, h1),), load_bytes=n_in, **tail(h1)),
            Shard("fc1", 1, 0, cols=((h1, n1),), comm_in_bytes=n_in,
                  comm_events=1, **tail(n1 - h1)),
            Shard("fc2", 2, 1, cols=((0, h2),), comm_in_bytes=n1,
                  comm_events=2, store_bytes=h2, **tail(h2)),
            Shard("fc2", 3, 1, cols=((h2, n2),), comm_in_bytes=n1,
                  comm_events=2, store_bytes=n2 - h2, **tail(n2 - h2)),
        ]
    else:
        raise ValueError(f"MLP mappings exist for 1/2/4 cores, not {cores}")
    suffix = "_fused" if fuse_epilogue else ""
    return CoreSchedule(program, shards, name=f"mlp_{cores}c{suffix}")


def _lstm_cell_cycles(nh: int, frac: float = 1.0, p=CALIB) -> float:
    """Digital cycles of the nine linear-complexity cell ops (§VIII-D),
    matching `workloads._lstm_cell_elemwise`."""
    m = int(nh * frac)
    ec = p.elem_cycles
    return (3 * m * ec["sigmoid"] + m * ec["tanh"] + 2 * m * ec["mul"]
            + m * ec["add"] + m * ec["tanh"] + m * ec["mul"])


def lstm_schedule(program: AimcProgram, cores: int, nh: int,
                  x_dim: int = 50, y_dim: int = 50,
                  p=CALIB) -> CoreSchedule:
    """The paper's LSTM analog mappings (Table II-B) over entries
    cell ([h,x] -> 4 gates side by side) and dense.

    cores=1 -> case 1/2 (everything one core); cores=2 -> case 3 (cell core
    + dense core); cores=5 -> case 4 (cell gate-sliced over four cores —
    each takes one column stripe of EVERY gate, exchanges h stripes
    all-to-all for the recurrence — plus a dense core)."""
    soft = p.elem_cycles["softmax"] * y_dim
    if cores == 1:
        shards = [Shard("cell", 0, 0, load_bytes=x_dim,
                        digital_cycles=_lstm_cell_cycles(nh, p=p)),
                  Shard("dense", 0, 1, store_bytes=y_dim,
                        digital_cycles=soft)]
    elif cores == 2:
        shards = [Shard("cell", 0, 0, load_bytes=x_dim,
                        digital_cycles=_lstm_cell_cycles(nh, p=p)),
                  Shard("dense", 1, 1, comm_in_bytes=nh, comm_events=1,
                        store_bytes=y_dim, digital_cycles=soft)]
    elif cores == 5:
        q = 4
        if nh % q:
            raise ValueError(f"gate slicing needs nh % {q} == 0, got {nh}")
        sl = nh // q
        shards = [
            Shard("cell", j, 0,
                  cols=tuple((g * nh + j * sl, g * nh + (j + 1) * sl)
                             for g in range(4)),
                  load_bytes=x_dim,
                  comm_in_bytes=(q - 1) * sl,       # h stripes from peers
                  comm_out_bytes=sl,                # own h stripe broadcast
                  comm_events=q,                    # q-1 in + 1 out
                  digital_cycles=_lstm_cell_cycles(nh, 1 / q, p=p))
            for j in range(q)
        ]
        shards.append(Shard("dense", q, 1, comm_in_bytes=nh, comm_events=1,
                            store_bytes=y_dim, digital_cycles=soft))
    else:
        raise ValueError(f"LSTM mappings exist for 1/2/5 cores, not {cores}")
    return CoreSchedule(program, shards, name=f"lstm_{cores}c")


def cnn_schedule(program: AimcProgram, convs: Sequence[tuple],
                 img: int = 224, p=CALIB) -> CoreSchedule:
    """The paper's pipelined CNN mapping (§IX-A): conv layer i on core i as
    pipeline stage i, feature maps handed core-to-core. ``convs`` is the
    `models.paper_nets.CNN_SPECS` row: (cin, k, cout, stride, pad, lrn,
    pool) per layer; output-position counts derive from `img`. The dense
    head stays digital (paper §IX-A) and is not part of this schedule."""
    shards = []
    ec = p.elem_cycles
    hw, c_prev = img, convs[0][0]
    for i, (cin, k, cout, stride, pad, lrn, pool) in enumerate(convs):
        out_hw = (hw + 2 * pad - k) // stride + 1
        in_bytes = hw * hw * c_prev
        elems = out_hw * out_hw * cout
        cycles = elems * ec["relu"]
        if lrn:
            cycles += elems * ec["lrn"]
        if pool > 1:
            cycles += elems * ec["maxpool"]
        shards.append(Shard(
            f"conv{i}", core=i, phase=i, count=out_hw * out_hw,
            load_bytes=in_bytes if i == 0 else 0,
            comm_in_bytes=0 if i == 0 else in_bytes,
            comm_events=0 if i == 0 else 1,
            digital_cycles=cycles))
        hw, c_prev = out_hw // pool, cout
    return CoreSchedule(program, shards, pipelined=True,
                        name=f"cnn_{len(convs)}stage")
