"""ServeEngine: request-level continuous batching over a programmed AIMC
model (the `runtime/` serving subsystem).

ALPINE's deployment model is weights-stationary inference (§IV-B, Fig. 4):
CM_INITIALIZE happens once, outside the region of interest, and serving is a
forever-loop of queue/process/dequeue token vectors. This module is that
loop made real at the REQUEST level, modeled on the saxml server split
(servable model owns jitted device functions; a host-side driver owns slots
and admission):

  request lifecycle   queued -> admitted -> prefilled -> [slot i] decoding
                      -> retired (EOS / length / max_new) -> slot refilled

  slot state machine  a fixed batch of ``n_slots`` decode lanes. Each lane
                      is FREE or holds one request. Prefill runs per request
                      at one padded shape [1, prompt_pad] (ragged prompts
                      via ``valid_len``), the resulting KV/recurrent state
                      is inserted into the lane at the request's own length,
                      and the dense decode batch advances every lane at
                      once — retired/free lanes compute but are bit-frozen
                      (transformer: their K/V rows are dropped and their
                      length held inside `decode_step`; recurrent state:
                      `mask_batch_select`), so they never corrupt state or
                      accounting.

  chunked decode      the decode closure advances ``decode_chunk`` steps
                      inside ONE jitted `lax.scan` (DESIGN.md §13). The
                      retirement predicates (max_new / EOS / max_seq cap)
                      are traced, so the active mask, per-slot token and
                      position counters live ON DEVICE for the whole chunk;
                      the host syncs once per chunk, reading a [k, n_slots]
                      token block plus per-step active/reason rows it
                      mirrors into the per-request books. `serve()` double-
                      buffers: chunk i+1 is dispatched before chunk i's
                      token block is read, so host bookkeeping and
                      admission overlap device compute.

  shape stability     exactly three device closures exist — prefill
                      [1, prompt_pad], insert (slot index is a traced
                      scalar), decode ([n_slots, 1] x decode_chunk scanned
                      steps) — each compiled ONCE at warmup. No shape
                      depends on arrival order, prompt length, or
                      live-request count, so a ragged Poisson trace runs
                      the whole session on the warmup executables
                      (asserted by `compile_counts`).

The decode loop is wrapped in `fault_tolerance.resilient_step` (transient
device errors retry; terminal ones — e.g. RESOURCE_EXHAUSTED — raise) and
timed by a `fault_tolerance.StragglerMonitor`.

CM_* accounting: every USEFUL token vector (prompt tokens at prefill, one
vector per decode step a request rides in) is booked to its request's
`RequestRecord`; padding lanes (prompt pad, idle slots) are tracked
separately as waste. `batcher.reconcile` proves the per-request ledgers sum
exactly to ``program.mvm_counts().scaled(total_vectors)``.

`launch.steps.make_prefill_step` / `make_serve_step` build their device
functions from this module's closure builders (`static_prefill_closure`,
`static_decode_closure`), so the static shape cells and the engine serve
through one implementation of the model-facing math.

Public surface
  * `ServeEngine`         — single-device continuous batching: `warmup()`,
    `serve(requests) -> ServeReport`, `compile_counts()`, `ledgers()` /
    `core_ledgers()` (CM_* books).
  * `EngineSession` + the session primitives `begin()` / `admit()` /
    `step()` / `cancel_active()` / `finish()` — the serving loop decomposed
    so an external driver (the multi-tenant `runtime.server.ModelServer`)
    can interleave several engines under ONE clock. `serve()` is exactly
    these primitives driven by a single `Batcher`.
  * `ShardedServeEngine`  — the same loop over a JAX mesh (DESIGN.md §11):
    slots over `data`, crossbar bit lines over `model`; adds
    `device_ledgers()`. Bit-equal to `ServeEngine` on the same trace.
  * `ServeReport`         — everything one serve run produced.
  * `static_generate`, `static_prefill_closure`, `static_decode_closure`
    — the legacy static-batch oracle and the shared model-facing math.

Invariants (pinned by tests/test_engine.py, tests/test_sharded_engine.py)
  * shape stability: after `warmup()` every closure's executable cache
    holds exactly one entry, for any trace, on any mesh;
  * synchronized arrivals are bit-equal to `static_generate`; the sharded
    engine is bit-equal to the single-device engine on ANY trace; decode
    is bit-equal across `decode_chunk` sizes (tests/test_chunked_decode.py);
  * slot reuse never leaks state (retired lanes are bit-frozen);
  * per-request ledgers reconcile exactly with `program.mvm_counts()`.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro import tracing
from repro.models.layers import (Execution, at_stated_precision,
                                 mask_batch_select)
from repro.models.moe import serves_dropless
from repro.runtime.batcher import (Batcher, Request, RequestRecord,
                                   SlotAllocator, percentile)
from repro.runtime.fault_tolerance import StragglerMonitor, resilient_step

RECURRENT_MODULES = ("xlstm", "rglru")


# ---------------------------------------------------------------------------
# closure builders — the model-facing math, shared with launch.steps
# ---------------------------------------------------------------------------

def static_prefill_closure(model, cfg, exe: Execution, *, family: str = "lm",
                           module: str = "transformer", max_seq: int,
                           cache_dtype) -> Callable:
    """(params, batch dict) -> (next_tok [B,1] int32, cache).

    The static-batch prefill math: one call covers audio (enc-dec), vlm,
    transformer and recurrent families. `launch.steps.make_prefill_step`
    jits exactly this; the engine's static A/B baseline reuses it."""
    if family == "audio":
        def prefill(params, batch):
            logits, cache = model.prefill(params, batch["frames"],
                                          batch["tokens"], cfg, exe,
                                          max_seq=max_seq,
                                          cache_dtype=cache_dtype)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache
    elif family == "vlm":
        def prefill(params, batch):
            logits, cache = model.prefill(params, batch["tokens"], cfg, exe,
                                          max_seq=max_seq,
                                          patch_embeds=batch["patch_embeds"],
                                          cache_dtype=cache_dtype)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache
    elif module == "transformer":
        def prefill(params, batch):
            logits, cache = model.prefill(params, batch["tokens"], cfg, exe,
                                          max_seq=max_seq,
                                          cache_dtype=cache_dtype)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache
    else:
        # recurrent families: forward-only lowering (the dry-run cells carry
        # no cache; slot-cache prefill is `model.prefill`, used by the
        # engine's per-request closure below)
        def prefill(params, batch):
            logits, _ = model.forward(params, batch["tokens"], cfg, exe)
            return jnp.argmax(logits[:, -1:], -1).astype(jnp.int32), ()
    return at_stated_precision(prefill, exe)


def static_decode_closure(model, cfg, exe: Execution) -> Callable:
    """(params, cache, tokens [B,1]) -> (next_tok [B,1] int32, cache) —
    the lockstep decode step `launch.steps.make_serve_step` jits."""
    def serve_step(params, cache, tokens):
        logits, new_cache = model.decode_step(params, cache, tokens, cfg, exe)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_cache
    return at_stated_precision(serve_step, exe)


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeReport:
    """Everything one `ServeEngine.serve` run produced."""
    records: dict[int, RequestRecord]
    n_steps: int = 0               # decode batch steps executed
    n_prefills: int = 0
    idle_vectors: int = 0          # frozen decode lanes (slot-idle waste)
    prefill_pad_vectors: int = 0   # prompt-padding lanes (prefill waste)
    # useful vectors counted FROM THE DEVICE LOOP (prompt lengths at the
    # prefill call + the scan's per-step active-lane counts read back with
    # each chunk) — independent of the per-request RequestRecord
    # bookkeeping, so the two can actually disagree if the engine double-
    # or under-counts (reconcile's job)
    observed_vectors: int = 0
    # sparse-expert models: distinct experts that received rows, summed
    # over every layer of every decode step (counted on the device, read
    # back with each chunk; divide by n_steps x layers x experts for the
    # share of the expert stacks a step reads)
    experts_touched: int = 0
    wall_prefill_s: float = 0.0
    wall_decode_s: float = 0.0
    makespan_s: float = 0.0        # engine clock: last retirement - start
    retries: int = 0
    stragglers: list = dataclasses.field(default_factory=list)
    # drift-aware serving books (runtime.health / runtime.chaos): probes
    # run, faults fired, hot recalibrations performed — and the extra
    # CM_INITIALIZE device writes they charged (NEVER silent; reconciled by
    # health.reconcile_recal against reprogram_counts recomputed from
    # shapes). wall_health_s is the probe+repair wall, billed apart from
    # decode so chunk timing stays honest under recovery.
    probes: int = 0
    n_recals: int = 0
    recal_initialize: int = 0
    recal_events: list = dataclasses.field(default_factory=list)
    fault_events: list = dataclasses.field(default_factory=list)
    wall_health_s: float = 0.0
    # paged serving books (DESIGN.md §15; defaults keep dense runs
    # untouched): prefix reuse, chunked-prefill legs, and the page-pool
    # ledger snapshot taken at finish() — every page attributed to exactly
    # one owner or the free list (`page_ledger_exact` is the allocator's
    # exact-partition verify()).
    # capacity-overflow rotation books (core.placement / DESIGN.md §16):
    # state swaps performed, the CM_INITIALIZE writes they charged (per
    # `AimcProgram.reprogram_counts` on each swap's incoming group —
    # reconciled exactly by placement.reconcile_swaps), and the host wall
    # spent swapping (billed apart from decode, overlap-exempt like
    # wall_health_s).
    n_swaps: int = 0
    swap_initialize: int = 0
    swap_events: list = dataclasses.field(default_factory=list)
    wall_swap_s: float = 0.0
    prefix_hits: int = 0           # admissions that reused >= 1 page/snapshot
    prefix_hit_vectors: int = 0    # prompt vectors NOT re-prefilled (shared span)
    prefill_chunks: int = 0        # prefill legs executed
    page_evictions: int = 0
    page_ledger: dict = dataclasses.field(default_factory=dict)
    page_ledger_exact: bool = True
    prefix_stats: dict = dataclasses.field(default_factory=dict)

    @property
    def useful_vectors(self) -> int:
        return sum(r.vectors for r in self.records.values())

    @property
    def generated_tokens(self) -> int:
        return sum(len(r.tokens) for r in self.records.values())

    def tokens(self, rid: int) -> list[int]:
        return self.records[rid].tokens

    def latency_percentiles(self, qs=(50, 99)) -> dict[str, float]:
        lats = [r.latency for r in self.records.values()]
        ttfts = [r.ttft for r in self.records.values()]
        out = {}
        for q in qs:
            out[f"p{q}_latency_s"] = percentile(lats, q)
            out[f"p{q}_ttft_s"] = percentile(ttfts, q)
        return out

    def summary(self) -> str:
        pct = self.latency_percentiles()
        return (f"{len(self.records)} requests, {self.generated_tokens} "
                f"tokens in {self.makespan_s:.2f}s engine-time "
                f"({self.n_prefills} prefills, {self.n_steps} "
                f"decode steps, {self.idle_vectors} idle lanes); "
                f"p50/p99 latency {pct['p50_latency_s']:.2f}/"
                f"{pct['p99_latency_s']:.2f}s")


@dataclasses.dataclass
class EngineSession:
    """Host-side state of one in-flight serving run.

    Owned by a `ServeEngine`, created by `ServeEngine.begin()`; every field
    the old monolithic `serve()` loop kept as a local lives here so an
    external driver (`runtime.server.ModelServer`) can interleave sessions
    of SEVERAL engines under one clock. Device buffers (``cache``,
    ``tok_buf``, ``state``) are reassigned by `admit`/`step` (insert
    donates), so a session must only ever be driven by its own engine's
    primitives. ``state`` is the DEVICE-resident per-lane retirement rows
    ({active, gen, pos, max_new}, each [n_slots]) — the host never
    rebuilds the active mask; it only mirrors retirement decisions read
    back with each chunk's ys."""
    report: ServeReport
    slots: SlotAllocator
    slot_rec: dict[int, RequestRecord]    # slot -> live record
    cache: object
    tok_buf: object
    state: object                          # device retirement rows (see above)
    retries0: int                          # lifetime counters at begin()
    flagged0: int
    # host-side projection of each busy lane's remaining length/cap budget
    # (slot -> steps). The chunk dispatcher picks the largest compiled
    # ladder length that some lane can still use — EOS may retire a lane
    # earlier than projected (bounded waste), never later.
    rem: dict[int, int] = dataclasses.field(default_factory=dict)
    # (record, first-token device handle) pairs whose prefill result the
    # host has NOT read yet: with no EOS configured nothing about admission
    # depends on the token's value, so the read defers to the next chunk
    # sync instead of stalling the host behind an in-flight chunk.
    lazy: list = dataclasses.field(default_factory=list)
    # paged serving (DESIGN.md §15): queued chunked-prefill jobs (FIFO, one
    # leg advanced per serve-loop iteration), the pages each busy slot holds
    # as (owned pids, shared-hit pids), and the prefix-cache counters at
    # begin() so the report shows THIS session's hits/evictions only.
    jobs: list = dataclasses.field(default_factory=list)
    slot_pages: dict = dataclasses.field(default_factory=dict)
    evictions0: int = 0
    hits0: int = 0
    misses0: int = 0


# traced retirement codes emitted by the decode scan (0 = still running);
# priority eos > length > cap, matching the pre-chunk host loop
_REASONS = {1: "length", 2: "eos", 3: "cap"}


@dataclasses.dataclass
class _PendingChunk:
    """One in-flight decode chunk: the scan's device outputs plus the
    dispatch-time clock marks `_process_chunk` needs to bill wall time
    without double-counting admissions that overlap the chunk."""
    ys: tuple          # (toks [n,S], active [n,S], reason [n,S])
    t_wall: float      # perf_counter at dispatch
    prefill0: float    # report.wall_prefill_s at dispatch
    n: int             # dispatched chunk length (a ladder size)
    health0: float = 0.0   # report.wall_health_s at dispatch (overlap bill)
    recals0: int = 0       # report.n_recals at dispatch (straggler exemption)
    swap0: float = 0.0     # report.wall_swap_s at dispatch (overlap bill)
    swaps0: int = 0        # report.n_swaps at dispatch (straggler exemption)


@dataclasses.dataclass
class _PrefillJob:
    """One admitted request whose (chunked) prefill has not finished.

    The remaining legs either drain synchronously at `admit` (``drain=True``
    — external drivers need admission to complete before they hand the
    clock elsewhere) or run one per serve-loop iteration interleaved with
    decode chunks (`_advance_prefill`). The slot is held for the job's whole
    life — its decode lane stays inactive on device — until `_finalize_job`
    registers the page table row / recurrent state and arms the lane."""
    req: Request
    rec: RequestRecord
    slot: int
    legs: list                # [(pos0, span, tokens [1, C])] in order
    leg_i: int = 0
    pt_row: object = None     # transformer: device [M] int32 page-table row
    keys: list = dataclasses.field(default_factory=list)
    f_eff: int = 0            # pages reused from the prefix cache
    carry: object = None      # recurrent: carried state between legs
    tok1: object = None       # [1,1] first-token handle from the last leg

    @property
    def done(self) -> bool:
        return self.leg_i >= len(self.legs)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class ServeEngine:
    """Continuous-batching serving engine over one installed model.

    Owns: the (program-installed) parameter tree, the slot-shaped decode
    cache, and the three jitted closures. Drives: admission (`Batcher`),
    slot allocation, retirement, refill, per-request accounting.

    ``params`` should already carry installed `AimcLinearState`s when
    serving the programmed AIMC path (``program.install(params)``); pass
    the `AimcProgram` as ``program`` for CM_* ledger reconciliation.
    """

    def __init__(self, model, cfg, exe: Execution, params, *,
                 n_slots: int = 4, prompt_pad: int = 16, max_seq: int = 64,
                 cache_dtype=jnp.float32, family: str = "lm",
                 module: str = "transformer", program=None, schedule=None,
                 eos_id: int | None = None, pad_id: int = 0,
                 max_retries: int = 2, straggler_threshold: float = 3.0,
                 admission: str = "fifo", decode_chunk: int = 1,
                 health=None, chaos=None, heartbeat=None,
                 page_size: int = 0, n_pages: int = 0,
                 prefix_cache: bool = False, prefill_chunk: int = 0,
                 rotation=None, rotation_params=None):
        if family == "audio":
            raise ValueError("ServeEngine serves decoder-only LMs; the "
                             "enc-dec audio family decodes via launch.steps")
        if prompt_pad > max_seq:
            raise ValueError(f"prompt_pad {prompt_pad} > max_seq {max_seq}")
        if family == "vlm" and prompt_pad < cfg.n_patches:
            raise ValueError(
                f"vlm prompts start with {cfg.n_patches} patch positions; "
                f"prompt_pad {prompt_pad} cannot hold them")
        self.model, self.cfg, self.exe, self.params = model, cfg, exe, params
        self.n_slots, self.prompt_pad, self.max_seq = n_slots, prompt_pad, max_seq
        self.cache_dtype = cache_dtype
        self.family, self.module = family, module
        self.program, self.schedule = program, schedule
        self.eos_id, self.pad_id = eos_id, pad_id
        self.admission = admission
        if decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got {decode_chunk}")
        self.decode_chunk = decode_chunk
        self._ladder = self._chunk_ladder(decode_chunk)
        self.recurrent = module in RECURRENT_MODULES

        # ---- paged KV / prefix cache / chunked prefill (DESIGN.md §15) ----
        if page_size < 0 or n_pages < 0 or prefill_chunk < 0:
            raise ValueError("page_size / n_pages / prefill_chunk >= 0")
        if (prefix_cache or prefill_chunk) and page_size == 0:
            raise ValueError("prefix_cache / prefill_chunk require "
                             "page_size > 0")
        if page_size > max_seq:
            raise ValueError(f"page_size {page_size} > max_seq {max_seq}")
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        # _paged_kv: the slot KV cache lives in a page pool behind a traced
        # page table (transformer families). _use_legs: prefill runs as
        # `prefill_chunk`-wide legs writing pages directly (needed by both
        # the prefix cache and chunked prefill). Recurrent archs have O(1)
        # state, so "paging" means snapshot pages (_snap) + chunked legs
        # (_legs_rec) instead of a paged decode cache.
        self._paged_kv = page_size > 0 and not self.recurrent
        self._legs_rec = (self.recurrent and page_size > 0
                          and (prefix_cache or prefill_chunk > 0))
        self._snap = self._legs_rec and prefix_cache
        self._use_legs = self._paged_kv and (prefix_cache or prefill_chunk > 0)
        self._chunked = prefill_chunk > 0
        if self._paged_kv and module != "transformer":
            raise ValueError(f"paged KV serves the transformer module; "
                             f"got {module!r}")
        self._pt_width = -(-max_seq // page_size) if self._paged_kv else 0
        # the dense decode closure of a dropless sparse-expert transformer
        # reads back the experts each step touched
        self._count_experts = (
            module == "transformer" and not self._paged_kv
            and getattr(cfg, "is_moe", False)
            and serves_dropless(params["blocks"].get("we_gate"), exe,
                                getattr(self, "mesh", None)))
        if self._use_legs:
            if family == "vlm":
                raise ValueError(
                    "prefix_cache / prefill_chunk cannot serve vlm (patch "
                    "embeds cannot ride a chunked prefill leg)")
            if getattr(cfg, "is_moe", False):
                raise ValueError(
                    "prefix_cache / prefill_chunk cannot serve MoE models: "
                    "routing is dropless, but each expert's DAC scale is the "
                    "max over the rows a call routes to it, so a prefill "
                    "leg does not quantise as the whole prompt does")
            if cache_dtype != jnp.float32:
                raise ValueError(
                    "prefix_cache / prefill_chunk require cache_dtype "
                    "float32: a page read back by a sharer must be bit-"
                    "identical to what the producing leg computed")
        if self._snap and self._chunked and prefill_chunk % page_size:
            raise ValueError(
                "recurrent prefix_cache requires prefill_chunk to be a "
                "multiple of page_size (snapshots are taken at leg ends, "
                "which must land on page boundaries)")
        self.pages = None
        self.prefix = None
        self._pool = None        # engine-lifetime (kp, vp) pool handles
        self._pool_snap = None   # engine-lifetime recurrent snapshot pool
        if self._paged_kv or self._snap:
            from repro.runtime.pages import PageAllocator, PrefixCache
            if n_pages == 0:
                n_pages = (n_slots * self._pt_width + 1
                           + (self._pt_width if prefix_cache else 0)
                           if self._paged_kv
                           else 1 + n_slots * max(1, prompt_pad // page_size))
            if self._paged_kv and n_pages < self._pt_width + 1:
                raise ValueError(
                    f"n_pages {n_pages} cannot hold one max-length request "
                    f"({self._pt_width} pages + scratch): admission would "
                    f"deadlock on an empty engine")
            self.pages = PageAllocator(n_pages, page_size)
            self.prefix = PrefixCache(self.pages) if prefix_cache else None
        # leg width: transformer legs default to one full-prompt leg;
        # recurrent legs to one page (snapshot boundaries = leg ends)
        self._leg_c = ((prefill_chunk or prompt_pad) if not self.recurrent
                       else (prefill_chunk or page_size))

        self.monitor = StragglerMonitor(threshold=straggler_threshold)
        self._retries = 0
        self._step_no = 0          # engine-lifetime decode step counter
        self._chunks_dispatched = 0  # lifetime chunk counter (chaos clock)
        # drift-aware serving (DESIGN.md §14): a `runtime.health.
        # HealthMonitor` evolves the installed states with program age,
        # probes them at chunk boundaries, and hot-reprograms failing
        # cores; a `runtime.chaos.FaultInjector` fires deterministic
        # kill/corrupt events on the chunk-dispatch clock. Both act ONLY
        # between chunks (`_resilience_tick`), so in-flight requests are
        # never touched. `heartbeat` (fault_tolerance.Heartbeat) makes the
        # loop's liveness visible to an external supervisor.
        self.health, self.chaos, self.heartbeat = health, chaos, heartbeat
        if health is not None:
            if program is None:
                raise ValueError("health monitoring requires an AimcProgram")
            if tuple(health.program.names) != tuple(program.names):
                raise ValueError("health monitor was built for a different "
                                 "program (matrix names mismatch)")
        if chaos is not None and health is None:
            raise ValueError("chaos injection requires a HealthMonitor to "
                             "detect and repair the faults it fires")

        # ---- capacity-overflow rotation (core.placement, DESIGN.md §16) ----
        # A `RotationPlan` time-multiplexes analog layer groups through a
        # tile budget the model exceeds: the engine holds ONE uncapped
        # program plus one installed parameter tree PER rotation state
        # (`AimcProgram.install_subset` — layers outside a state serve
        # digitally from the raw weights), and `_placement_tick` advances
        # the state at chunk boundaries, billing each swap's incoming
        # group as CM_INITIALIZE. Different states install different
        # leaves (different treedefs), so each state compiles its own
        # prefill/decode executables — ALL warmed in `warmup`.
        self.rotation = rotation
        self._rotation_params = (tuple(rotation_params)
                                 if rotation_params is not None else None)
        self._rot_state = 0
        self._swaps_done = 0
        if rotation is not None:
            if program is None:
                raise ValueError("rotation serving requires the backing "
                                 "AimcProgram (swap billing is shape-based)")
            if (self._rotation_params is None
                    or len(self._rotation_params) != rotation.n_states):
                got = (len(self._rotation_params)
                       if self._rotation_params is not None else None)
                raise ValueError(
                    f"rotation needs one installed parameter tree per "
                    f"state ({rotation.n_states}), got {got}")
            if health is not None or chaos is not None:
                raise ValueError(
                    "rotation cannot combine with health/chaos: a hot "
                    "recal would repair only the current state's tree")
            if prefix_cache or prefill_chunk:
                raise ValueError(
                    "rotation cannot combine with prefix_cache / "
                    "prefill_chunk: a cached span replayed under a "
                    "different rotation state would not be bit-stable")
            self.params = self._rotation_params[0]

        # per-leaf batch axes of the decode cache (probed, not hardcoded:
        # transformer KV stacks batch at axis 1, recurrent state trees too,
        # but "len" and any future leaf may differ — shape-diffing two
        # abstract init_cache calls finds the axis without model knowledge)
        self._axes = self._probe_batch_axes()
        self._build_closures(max_retries)

    @staticmethod
    def _chunk_ladder(k: int) -> tuple[int, ...]:
        """The compiled chunk lengths: every power of two up to ``k``, plus
        ``k`` itself. ALL ladder lengths compile at warmup; the dispatcher
        then picks per chunk (`_pick_chunk`), so serving never recompiles
        whatever mix of lengths a ragged trace needs."""
        ladder = {1, k}
        p = 2
        while p < k:
            ladder.add(p)
            p *= 2
        return tuple(sorted(ladder))

    def _build_closures(self, max_retries: int):
        """Compile the device closures. `ShardedServeEngine` overrides
        this to pin every input/output to a mesh placement; the math
        (`_prefill_fn`/`_insert_fn`/`_decode_fn`) is shared verbatim."""
        self._jit_prefill = jax.jit(self._traced(self._prefill_fn))
        self._jit_insert = jax.jit(self._insert_fn,
                                   donate_argnums=(0, 2, 4))
        decode_fn = self._decode_fn
        if self._paged_kv:
            decode_fn = self._decode_paged_fn
            self._jit_insert_paged = jax.jit(self._insert_paged_fn,
                                             donate_argnums=(0, 2, 4))
        if self._use_legs:
            self._jit_leg = jax.jit(self._traced(self._leg_fn),
                                    donate_argnums=(2, 3))
            self._jit_register = jax.jit(self._register_fn,
                                         donate_argnums=(0, 1, 2, 4))
        if self._legs_rec:
            self._jit_leg_rec = jax.jit(self._traced(self._leg_rec_fn),
                                        donate_argnums=(1,))
        if self._snap:
            self._jit_snap_put = jax.jit(self._snap_put_fn,
                                         donate_argnums=(0,))
            self._jit_snap_get = jax.jit(self._snap_get_fn)
        # the decode cache is NOT donated: the step runs under
        # resilient_step, and a retry after a transient failure must be able
        # to re-present the same input buffers (donation would have
        # invalidated them on the failed attempt)
        self._decode_jits = {
            n: jax.jit(self._traced(functools.partial(decode_fn, length=n)))
            for n in self._ladder}
        self._safe_decodes = {
            n: resilient_step(f, max_retries=max_retries,
                              on_retry=lambda attempt, e: self._count_retry())
            for n, f in self._decode_jits.items()}

    # -- closures ------------------------------------------------------------
    def _probe_batch_axes(self):
        def shapes(b):
            return jax.eval_shape(lambda: self.model.init_cache(
                self.cfg, b, self.max_seq, self.cache_dtype))

        def axis_of(s2, s3):
            for i, (a, b) in enumerate(zip(s2.shape, s3.shape)):
                if a != b:
                    return i
            raise ValueError(f"no batch axis found in cache leaf {s2}")

        return jax.tree.map(axis_of, shapes(2), shapes(3))

    def _traced(self, fn):
        """A closure that runs model code, traced at the matmul precision
        the execution states (`Execution.matmul_precision`)."""
        return at_stated_precision(fn, self.exe)

    def _prefill_fn(self, params, tokens, valid_len):
        """[1, prompt_pad] ragged prefill -> (first_tok [1,1], cache1)."""
        kw = {}
        if self.family == "vlm":
            # patch positions are a prompt prefix; the engine serves the
            # text path with zero patch embeddings unless a request-level
            # frontend supplies them (frontend-stub rule)
            kw["patch_embeds"] = jnp.zeros(
                (tokens.shape[0], self.cfg.n_patches, self.cfg.d_model),
                jnp.float32)
        logits, cache = self.model.prefill(
            params, tokens, self.cfg, self.exe, max_seq=self.max_seq,
            cache_dtype=self.cache_dtype, valid_len=valid_len, **kw)
        with jax.named_scope(tracing.HEAD):
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        return tok, cache

    def _insert_fn(self, cache, cache1, tok_buf, tok1, state, slot, pos0,
                   max_new):
        """Write a prefilled request's state into decode lane ``slot`` —
        including the lane's on-device retirement row (active flag,
        generated-token count, KV position, decode budget), so the decode
        closure never needs a host-built mask."""
        def put(big, one, ax):
            return jax.lax.dynamic_update_slice_in_dim(
                big, one.astype(big.dtype), slot, axis=ax)

        cache = jax.tree.map(put, cache, cache1, self._axes)
        tok_buf = jax.lax.dynamic_update_slice(tok_buf, tok1, (slot, 0))
        # gen starts at 1: the prefill's first token counts against max_new
        state = {"active": state["active"].at[slot].set(True),
                 "gen": state["gen"].at[slot].set(1),
                 "pos": state["pos"].at[slot].set(pos0),
                 "max_new": state["max_new"].at[slot].set(max_new)}
        return cache, tok_buf, state

    def _decode_fn(self, params, cache, tok_buf, state, length):
        """``length`` dense decode steps in ONE jitted `lax.scan`; inactive
        lanes are bit-frozen — a transformer's `decode_step` takes the
        lane mask and writes only active lanes' K/V rows (no pass over the
        whole cache), recurrent state goes through `mask_batch_select`.
        Retirement predicates (max_new / EOS / max_seq cap) are traced,
        so the active mask and per-lane counters never leave the device
        mid-chunk. ``length`` is host-chosen per dispatch from the
        COMPILED LADDER (`_chunk_ladder`): the host mirrors every lane's
        length/cap budget exactly, so it picks the largest ladder length
        no greater than the longest remaining budget — a chunk never runs
        past the last live lane (the fixed-k variant over-ran ragged
        traces by 2-3x decode steps at k=8), and the device needs no
        early-exit predicate (an `active.any()` loop
        condition would be a per-token cross-device collective).

        Returns (tok_buf, cache, state, ys) with per-step chunk outputs
        ys = (toks [length,S], active-at-entry [length,S], reason
        [length,S]), and for a sparse-expert transformer served dropless
        (`moe.serves_dropless`) the experts each step touched over its
        layers ([length]). The per-step busy count
        is NOT reduced on device: `active.sum()` would be the only other
        cross-device collective in the data-sharded loop (one all-reduce
        per token) — the host pops it from the ``active`` rows it reads
        back anyway. ys rides outside
        ``state`` because a subsequent insert donates the state buffers
        while a chunk's readback may still be pending (double-buffered
        serve)."""
        def one_step(carry, _):
            cache, tokens, st = carry
            active = st["active"]
            touched = []
            if self.module == "transformer":
                # frozen lanes' K/V rows are dropped in place, their len held
                logits, cache, *touched = self.model.decode_step(
                    params, cache, tokens, self.cfg, self.exe, ragged=True,
                    active=active, count_experts=self._count_experts)
            else:
                logits, new_cache = self.model.decode_step(
                    params, cache, tokens, self.cfg, self.exe)
                # O(1) recurrent state per slot: the select is the freeze
                with jax.named_scope(tracing.LANE_FREEZE):
                    cache = jax.tree.map(
                        lambda n, o, ax: mask_batch_select(n, o, active, ax),
                        new_cache, cache, self._axes)
            with jax.named_scope(tracing.HEAD):
                tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            with jax.named_scope(tracing.LANE_FREEZE):
                tok = jnp.where(active[:, None], tok, tokens)
            emitted = tok[:, 0]
            gen = st["gen"] + active.astype(jnp.int32)
            pos = st["pos"] + active.astype(jnp.int32)
            done_len = gen >= st["max_new"]
            done_eos = (jnp.zeros_like(active) if self.eos_id is None
                        else emitted == jnp.int32(self.eos_id))
            # the KV write position is bounded by max_seq; O(1)-state
            # recurrent archs have no such cap
            done_cap = (jnp.zeros_like(active) if self.recurrent
                        else pos >= jnp.int32(self.max_seq))
            reason = jnp.where(done_eos, 2, jnp.where(done_len, 1,
                               jnp.where(done_cap, 3, 0))).astype(jnp.int32)
            reason = jnp.where(active, reason, 0)
            new_st = {"active": active & (reason == 0), "gen": gen,
                      "pos": pos, "max_new": st["max_new"]}
            return (cache, tok, new_st), (emitted, active, reason, *touched)

        (cache, tok_buf, state), ys = jax.lax.scan(
            one_step, (cache, tok_buf, state), None, length=length)
        return tok_buf, cache, state, ys

    # -- paged closures (DESIGN.md §15) --------------------------------------
    @staticmethod
    def _paged_axes():
        """Per-leaf data axes of the paged cache dict: the pools split at
        their page axis, the table and lengths at the slot axis."""
        return {"kp": 1, "vp": 1, "pt": 0, "len": 0}

    def _insert_paged_fn(self, cache, cache1, tok_buf, tok1, state, slot,
                         pos0, max_new, pt_row, write_mask):
        """Mode-A paged insert: scatter a DENSE [1, max_seq] prefill cache
        into this request's pages and point the slot's page-table row at
        them. ``write_mask`` keeps only the first n_alloc table entries
        (the pages actually allocated — rows past the request's reach hold
        prompt padding never read); masked-off writes route to SCRATCH."""
        p, m = self.page_size, self._pt_width
        n_rows = m * p

        def to_pages(leaf, pool):
            x = leaf[:, 0].astype(pool.dtype)      # [L, max_seq, H, hd]
            if n_rows != self.max_seq:
                pad = [(0, 0)] * x.ndim
                pad[1] = (0, n_rows - self.max_seq)
                x = jnp.pad(x, pad)
            return x.reshape(x.shape[0], m, p, *x.shape[2:])

        pids = jnp.where(write_mask, pt_row, 0)
        kp = cache["kp"].at[:, pids].set(to_pages(cache1["k"], cache["kp"]))
        vp = cache["vp"].at[:, pids].set(to_pages(cache1["v"], cache["vp"]))
        pt = jax.lax.dynamic_update_slice(cache["pt"], pt_row[None, :],
                                          (slot, 0))
        lens = cache["len"].at[slot].set(pos0)
        tok_buf = jax.lax.dynamic_update_slice(tok_buf, tok1, (slot, 0))
        state = {"active": state["active"].at[slot].set(True),
                 "gen": state["gen"].at[slot].set(1),
                 "pos": state["pos"].at[slot].set(pos0),
                 "max_new": state["max_new"].at[slot].set(max_new)}
        return {"kp": kp, "vp": vp, "pt": pt, "len": lens}, tok_buf, state

    def _register_fn(self, pt, lens, tok_buf, tok1, state, slot, pos0,
                     max_new, pt_row):
        """Arm a slot whose pages were filled in place by prefill LEGS
        (`_leg_fn`): only the small per-slot leaves change — the pools are
        not even passed through, so nothing copies them."""
        pt = jax.lax.dynamic_update_slice(pt, pt_row[None, :], (slot, 0))
        lens = lens.at[slot].set(pos0)
        tok_buf = jax.lax.dynamic_update_slice(tok_buf, tok1, (slot, 0))
        state = {"active": state["active"].at[slot].set(True),
                 "gen": state["gen"].at[slot].set(1),
                 "pos": state["pos"].at[slot].set(pos0),
                 "max_new": state["max_new"].at[slot].set(max_new)}
        return pt, lens, tok_buf, state

    def _leg_fn(self, params, tokens, kp, vp, pt_row, pos0, span):
        """One transformer prefill leg writing straight into pages."""
        return self.model.prefill_chunk(
            params, tokens, self.cfg, self.exe, kp, vp, pt_row, pos0, span,
            page_size=self.page_size, context_len=self.prompt_pad)

    def _leg_rec_fn(self, params, cache, tokens, span):
        """One recurrent prefill leg advancing a carried [1, ...] state."""
        logits, cache = self.model.prefill_chunk(
            params, cache, tokens, self.cfg, self.exe, span)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        return tok, cache

    def _snap_put_fn(self, pool, cache1, pid):
        """Store a [1, ...] recurrent state into snapshot page ``pid``."""
        def put(big, one, ax):
            return jax.lax.dynamic_update_slice_in_dim(
                big, one.astype(big.dtype), pid, axis=ax)
        return jax.tree.map(put, pool, cache1, self._axes)

    def _snap_get_fn(self, pool, pid):
        """Read snapshot page ``pid`` back as a [1, ...] state tree."""
        return jax.tree.map(
            lambda big, ax: jax.lax.dynamic_slice_in_dim(big, pid, 1,
                                                         axis=ax),
            pool, self._axes)

    def _decode_paged_fn(self, params, cache, tok_buf, state, length):
        """The paged twin of `_decode_fn`: same scanned retirement machine,
        but the KV cache is a page pool behind a traced page table.

        Per step: gather the table into a dense [S, max_seq] VIEW (pure
        indexing — `transformer.paged_view`), run the IDENTICAL ragged
        `decode_step`, then scatter each lane's one written row back to its
        page. The view rows a lane actually attends to were produced by the
        same ops as the dense cache rows (prefill or a previous readback-
        identical scatter), and `decode_attention` masks pre-softmax, so
        decode is BIT-EQUAL to the dense engine. Inactive lanes scatter to
        the reserved SCRATCH page — the paged twin of the dense engine's
        dropped rows (their table rows may be stale after retirement;
        scratch absorbs the write and the gathered view is masked by
        ``len``). The view is a per-step temporary, so `decode_step` runs
        without ``active`` here and every lane's row is read back."""
        pt = cache["pt"]
        p = self.page_size
        rows = jnp.arange(self.n_slots)

        def one_step(carry, _):
            kp, vp, lens, tokens, st = carry
            active = st["active"]
            k_view, v_view = self.model.paged_view(kp, vp, pt, self.max_seq)
            dense = {"k": k_view, "v": v_view, "len": lens}
            logits, new_cache = self.model.decode_step(
                params, dense, tokens, self.cfg, self.exe, ragged=True)
            # the one row decode_step wrote, per lane (its pre-step length)
            row = jnp.clip(lens, 0, self.max_seq - 1)
            k_row = new_cache["k"][:, rows, row]      # [L, S, H, hd]
            v_row = new_cache["v"][:, rows, row]
            pid = jnp.take_along_axis(pt, (row // p)[:, None], axis=1)[:, 0]
            spid = jnp.where(active, pid, 0)          # inactive -> SCRATCH
            soff = jnp.where(active, row % p, 0)
            kp = kp.at[:, spid, soff].set(k_row)
            vp = vp.at[:, spid, soff].set(v_row)
            lens = jnp.where(active, new_cache["len"], lens)
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            tok = jnp.where(active[:, None], tok, tokens)
            emitted = tok[:, 0]
            gen = st["gen"] + active.astype(jnp.int32)
            pos = st["pos"] + active.astype(jnp.int32)
            done_len = gen >= st["max_new"]
            done_eos = (jnp.zeros_like(active) if self.eos_id is None
                        else emitted == jnp.int32(self.eos_id))
            done_cap = pos >= jnp.int32(self.max_seq)
            reason = jnp.where(done_eos, 2, jnp.where(done_len, 1,
                               jnp.where(done_cap, 3, 0))).astype(jnp.int32)
            reason = jnp.where(active, reason, 0)
            new_st = {"active": active & (reason == 0), "gen": gen,
                      "pos": pos, "max_new": st["max_new"]}
            return (kp, vp, lens, tok, new_st), (emitted, active, reason)

        (kp, vp, lens, tok_buf, state), ys = jax.lax.scan(
            one_step, (cache["kp"], cache["vp"], cache["len"], tok_buf,
                       state), None, length=length)
        return tok_buf, {"kp": kp, "vp": vp, "pt": pt, "len": lens}, \
            state, ys

    # -- warmup / compile accounting ----------------------------------------
    @staticmethod
    def _commit_ambient(tree):
        """Commit creation-fresh buffers to the replicated placement of the
        ambient mesh, if one is active. Under `jax.set_mesh`, jit OUTPUTS come
        back NamedSharding-committed while `jnp.zeros` stays uncommitted —
        and the executable cache keys on placement, so a closure fed a
        fresh buffer at session start and a jit output afterwards would
        compile twice. No ambient mesh: identity (placement is uniform)."""
        import jax.sharding as shd

        from repro.compat import current_mesh
        mesh = current_mesh()
        if mesh is None:
            return tree
        sh = shd.NamedSharding(mesh, shd.PartitionSpec())
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)

    def _fresh_pools(self):
        """Zero-filled (kp, vp) page pools. Overridden by the sharded
        engine to create them on the mesh placement."""
        pools = self.model.init_paged_cache(
            self.cfg, self.pages.n_pages, self.page_size, self.cache_dtype)
        return self._commit_ambient((pools["kp"], pools["vp"]))

    def _paged_cache_dict(self, kp, vp):
        """Assemble the paged slot cache around pool handles. The SINGLE
        place pt/len are created — warmup's throwaway cache and the session
        cache must key the insert closure's jit cache identically, so the
        sharded override commits them to the mesh here."""
        return {"kp": kp, "vp": vp,
                "pt": self._commit_ambient(
                    jnp.zeros((self.n_slots, self._pt_width), jnp.int32)),
                "len": self._commit_ambient(
                    jnp.zeros((self.n_slots,), jnp.int32))}

    def _empty_cache(self):
        if self._paged_kv:
            # the pools OUTLIVE sessions (prefix pages stay resident across
            # `begin()`s); the handles move into the session here and come
            # back at `finish()` — everything else is per-session zeros
            if self._pool is None:
                self._pool = self._fresh_pools()
            kp, vp = self._pool
            self._pool = None
            return self._paged_cache_dict(kp, vp)
        return self._commit_ambient(self.model.init_cache(
            self.cfg, self.n_slots, self.max_seq, self.cache_dtype))

    def _snap_pool(self):
        """The engine-lifetime recurrent snapshot pool, lazily created (its
        leaves are the slot cache's with n_pages in the batch axis)."""
        if self._pool_snap is None:
            self._pool_snap = self._commit_ambient(self.model.init_cache(
                self.cfg, self.pages.n_pages, self.max_seq,
                self.cache_dtype))
        return self._pool_snap

    def _empty_tok_buf(self):
        """The [n_slots, 1] next-token buffer. A hook so the sharded engine
        can commit it to its mesh placement — an uncommitted buffer would
        key the insert closure's jit cache differently from the committed
        buffers later steps feed back, costing a recompile."""
        return self._commit_ambient(jnp.zeros((self.n_slots, 1), jnp.int32))

    def _empty_state(self):
        """The device-resident per-lane retirement rows, all [n_slots]:
        active mask, generated-token and KV-position counters, decode
        budget. Sharded engine override commits them to the mesh. Each leaf
        must be a DISTINCT buffer — insert donates the whole dict, and XLA
        rejects donating one buffer twice."""
        def z():
            return jnp.zeros((self.n_slots,), jnp.int32)
        return self._commit_ambient(
            {"active": jnp.zeros((self.n_slots,), bool),
             "gen": z(), "pos": z(), "max_new": z()})

    def warmup(self):
        """Compile every closure (prefill, insert, and one decode
        executable per ladder length) once, outside the serving clock.
        Under rotation, prefill/decode compile once PER rotation state
        (states install different leaves, hence different treedefs), so
        mid-trace swaps never hit the serving clock with a compile."""
        tokens = jnp.zeros((1, self.prompt_pad), jnp.int32)
        vl = jnp.ones((1,), jnp.int32)
        param_sets = self._rotation_params or (self.params,)
        for ps in param_sets:
            tok1, cache1 = self._jit_prefill(ps, tokens, vl)
        tok_buf = self._empty_tok_buf()
        state = self._empty_state()
        if self._paged_kv:
            # THROWAWAY pools: insert/leg closures DONATE their pool
            # arguments, so warming them on the engine-lifetime pool would
            # invalidate it before the first session
            kp, vp = self._fresh_pools()
            cache = self._paged_cache_dict(kp, vp)
            pt_row = jnp.zeros((self._pt_width,), jnp.int32)
            cache, tok_buf, state = self._jit_insert_paged(
                cache, cache1, tok_buf, tok1, state, jnp.int32(0),
                jnp.int32(1), jnp.int32(1), pt_row,
                jnp.zeros((self._pt_width,), bool))
            if self._use_legs:
                leg_toks = jnp.zeros((1, self._leg_c), jnp.int32)
                tokw, kp2, vp2 = self._jit_leg(
                    self.params, leg_toks, cache["kp"], cache["vp"],
                    pt_row, jnp.int32(0), jnp.int32(1))
                cache["kp"], cache["vp"] = kp2, vp2
                pt2, len2, tok_buf, state = self._jit_register(
                    cache["pt"], cache["len"], tok_buf, tokw, state,
                    jnp.int32(0), jnp.int32(1), jnp.int32(1), pt_row)
                cache["pt"], cache["len"] = pt2, len2
        else:
            cache = self._empty_cache()
            if self._legs_rec:
                c1 = self._commit_ambient(self.model.init_cache(
                    self.cfg, 1, self.max_seq, self.cache_dtype))
                leg_toks = jnp.zeros((1, self._leg_c), jnp.int32)
                tokw, c1 = self._jit_leg_rec(self.params, c1, leg_toks,
                                             jnp.int32(1))
                if self._snap:
                    # throwaway snapshot pool, same reason as above; must
                    # carry the same ambient-mesh placement as _snap_pool()
                    # or snap_put compiles twice (warmup vs serve)
                    pool = self._commit_ambient(self.model.init_cache(
                        self.cfg, self.pages.n_pages, self.max_seq,
                        self.cache_dtype))
                    pool = self._jit_snap_put(pool, c1, jnp.int32(1))
                    jax.block_until_ready(
                        self._jit_snap_get(pool, jnp.int32(1)))
                # warm insert on the LEG RUNNER's outputs: serve-time
                # finalize always inserts a leg_rec-produced carry/token,
                # whose ambient-mesh placement differs from _jit_prefill's
                # (committed vs not) and would force a second executable
                cache, tok_buf, state = self._jit_insert(
                    cache, c1, tok_buf, tokw, state, jnp.int32(0),
                    jnp.int32(1), jnp.int32(1))
            else:
                cache, tok_buf, state = self._jit_insert(
                    cache, cache1, tok_buf, tok1, state, jnp.int32(0),
                    jnp.int32(1), jnp.int32(1))
        for ps in param_sets:
            for n in self._ladder:
                tok_buf, cache, state, ys = self._decode_jits[n](
                    ps, cache, tok_buf, state)
        jax.block_until_ready(ys)
        return self.compile_counts()

    def compile_counts(self) -> dict[str, int]:
        """Executable-cache sizes of the engine closures. After `warmup`,
        serving any trace must leave prefill/insert at 1 and decode at
        ``len(self._ladder)`` (one executable per compiled chunk length,
        all warmed up front) — the shape-stability contract (pinned by
        tests/test_engine.py and tests/test_chunked_decode.py)."""
        insert = (self._jit_insert_paged if self._paged_kv
                  else self._jit_insert)
        counts = {"prefill": self._jit_prefill._cache_size(),
                  "insert": insert._cache_size(),
                  "decode": sum(f._cache_size()
                                for f in self._decode_jits.values())}
        if self._use_legs:
            counts["prefill_chunk"] = self._jit_leg._cache_size()
            counts["register"] = self._jit_register._cache_size()
        if self._legs_rec:
            counts["prefill_chunk"] = self._jit_leg_rec._cache_size()
        if self._snap:
            counts["snapshot"] = self._jit_snap_put._cache_size()
            counts["restore"] = self._jit_snap_get._cache_size()
        return counts

    def _count_retry(self):
        self._retries += 1

    # -- drift / health / chaos (DESIGN.md §14) -------------------------------
    def _set_params(self, params):
        """Swap the served parameter tree. Every update preserves shapes and
        treedef (drift gains scale s_w; reprogrammed states are
        structure-identical), so the compiled closures are reused as-is.
        The sharded engine overrides this to re-pin the mesh placement."""
        self.params = params

    def _resilience_tick(self, sess: "EngineSession", now: float) -> float:
        """Chunk-boundary resilience work: fire due chaos events, advance
        drift, probe the live states, and hot-reprogram failing cores.

        Runs on the host BETWEEN chunk dispatches — an in-flight chunk was
        dispatched against the previous parameter tree and is untouched, so
        recovery never drops or perturbs an in-flight request. All wall
        time spent here is billed to ``wall_health_s`` (and subtracted from
        the overlapping chunk's decode bill in `_process_chunk`)."""
        if self.health is None and self.chaos is None:
            return now
        from repro.core.program import installed_entries
        from repro.runtime import chaos as chaos_lib
        from repro.runtime.health import RecalEvent
        t0 = time.perf_counter()
        report = sess.report
        forced = False
        if self.chaos is not None:
            for ev in self.chaos.due(self._chunks_dispatched):
                prog = self.health.program
                mag = 1.0 if ev.kind == chaos_lib.KILL else ev.magnitude
                entries = chaos_lib.corrupt_entries(prog, ev.core, mag)
                if ev.kind == chaos_lib.KILL:
                    self.health.mark_dead(ev.core)
                if entries:
                    self._set_params(
                        prog.install_updates(self.params, entries))
                report.fault_events.append(ev)
                forced = True
        if self.health is not None and (forced or self.health.due(now)):
            drifted = self.health.drifted_entries(now)
            if drifted:
                self._set_params(
                    self.health.program.install_updates(self.params, drifted))
            live = installed_entries(self.params)
            sample = self.health.probe(live, now)
            report.probes += 1
            failing = self.health.failing_cores(sample)
            if failing:
                dead = set(failing) & self.health.dead
                t_r = time.perf_counter()
                entries, names, cm = self.health.recalibrate(failing, now)
                if names:
                    prog = self.health.program
                    self.program = prog
                    if (self.schedule is not None
                            and self.schedule.name == "from_program"):
                        from repro.core.schedule import CoreSchedule
                        self.schedule = CoreSchedule.from_program(
                            prog, pipelined=self.schedule.pipelined)
                    self._set_params(
                        prog.install_updates(self.params, entries))
                    ev = RecalEvent(
                        t=now,
                        reason=("dead_core" if dead
                                else "fault" if forced else "drift"),
                        cores=tuple(failing), names=names,
                        initialize=cm.initialize,
                        wall_s=time.perf_counter() - t_r)
                    self.health.events.append(ev)
                    report.recal_events.append(ev)
                    report.recal_initialize += cm.initialize
                    report.n_recals += 1
        wall = time.perf_counter() - t0
        report.wall_health_s += wall
        return now + wall

    # -- capacity-overflow rotation (core.placement, DESIGN.md §16) ----------
    def _placement_tick(self, sess: "EngineSession", now: float) -> float:
        """Chunk-boundary rotation swap: when the swap cadence is due,
        advance ONE rotation state, install its parameter tree, and bill
        the incoming group's reprogram as CM_INITIALIZE plus the host wall
        spent swapping.

        Swaps land BETWEEN chunk dispatches only — the in-flight chunk ran
        entirely under the previous state's tree, so no token is ever
        produced by a half-swapped program. Decode lanes are row-
        independent and every state is bit-validated against the digital
        oracle separately (`launch.serve --placement-verify`), so the
        rotation schedule never changes what a request generates."""
        rot = self.rotation
        if rot is None or rot.n_states < 2:
            return now
        due = self._chunks_dispatched // rot.swap_every
        if due <= self._swaps_done:
            return now
        from repro.core.placement import SwapEvent
        t0 = time.perf_counter()
        report = sess.report
        self._swaps_done = due
        self._rot_state = (self._rot_state + 1) % rot.n_states
        self._set_params(self._rotation_params[self._rot_state])
        incoming = rot.incoming(self._rot_state)
        cm = self.program.reprogram_counts(incoming)
        wall = time.perf_counter() - t0
        ev = SwapEvent(t=now, chunk=self._chunks_dispatched,
                       state=self._rot_state, incoming=incoming,
                       initialize=cm.initialize, wall_s=wall)
        report.swap_events.append(ev)
        report.swap_initialize += cm.initialize
        report.n_swaps += 1
        report.wall_swap_s += wall
        return now + wall

    # -- request plumbing ----------------------------------------------------
    def _pad_prompt(self, prompt):
        if len(prompt) > self.prompt_pad:
            raise ValueError(f"prompt length {len(prompt)} exceeds "
                             f"prompt_pad {self.prompt_pad}")
        if self.family == "vlm" and len(prompt) < self.cfg.n_patches:
            # positions [0, n_patches) are patch embeddings wholesale; a
            # shorter prompt would gather its "last valid" logit inside the
            # patch prefix and serve silently wrong
            raise ValueError(
                f"vlm prompt length {len(prompt)} < n_patches "
                f"{self.cfg.n_patches}: the prompt must cover the patch "
                f"positions")
        padded = list(prompt) + [self.pad_id] * (self.prompt_pad - len(prompt))
        return (jnp.asarray(padded, jnp.int32)[None],
                jnp.asarray([len(prompt)], jnp.int32))

    def _prefill_request(self, req: Request, rec: RequestRecord,
                         lazy: bool = False):
        """Run the [1, prompt_pad] prefill; book the vector counts. The
        caller decides whether the first token is delivered output (an
        instant EOS is control, not payload — `admit`). With ``lazy`` the
        host does NOT block on the result: ``first`` comes back None and
        the caller reads the token handle at the next chunk sync — the
        prefill itself queues behind any in-flight chunk on the device, so
        blocking here would stall admission on decode compute."""
        with tracing.span("prefill", rid=req.rid):
            tokens, vl = self._pad_prompt(req.prompt)
            t0 = time.perf_counter()
            tok1, cache1 = self._jit_prefill(self.params, tokens, vl)
            first = None
            if not lazy:
                tok1.block_until_ready()
                first = int(tok1[0, 0])
            dt = time.perf_counter() - t0
        rec.prefill_vectors = len(req.prompt)
        rec.pad_vectors = self.prompt_pad - len(req.prompt)
        return tok1, cache1, first, dt

    # -- session primitives --------------------------------------------------
    # The serving loop decomposed into driver-steerable pieces: `serve()`
    # drives one session off a single `Batcher`; the multi-tenant
    # `runtime.server.ModelServer` drives one session PER co-resident model
    # under a shared clock with tenant-quota admission. Both produce
    # identical tokens for identical (request, admission-order) sequences —
    # the primitives only factor the loop, they never reorder it.

    def begin(self) -> "EngineSession":
        """Open a serving session: fresh slots, device buffers and books.

        Snapshots lifetime retry/straggler counters so a reused engine
        reports only THIS session's retries/flags (the EWMA baseline itself
        carries over on purpose — it stays warm across traces)."""
        px = self.prefix
        return EngineSession(
            report=ServeReport(records={}),
            slots=SlotAllocator(self.n_slots),
            slot_rec={},
            cache=self._empty_cache(),
            tok_buf=self._empty_tok_buf(),
            state=self._empty_state(),
            retries0=self._retries,
            flagged0=len(self.monitor.flagged),
            evictions0=px.evictions if px is not None else 0,
            hits0=px.hits if px is not None else 0,
            misses0=px.misses if px is not None else 0)

    @staticmethod
    def _retire(rec: RequestRecord, reason: str, at: float):
        rec.finish_reason = reason
        rec.t_done = at

    def admit(self, sess: "EngineSession", req: Request, now: float,
              drain: bool = True) -> float:
        """Admit one request at clock ``now``: prefill, book, and either
        retire at prefill (max_new=1 / instant EOS — the request never
        occupies a decode slot) or insert into a free slot. Returns the
        advanced clock. Caller guarantees ``sess.slots.n_free > 0`` (and,
        when paged, `can_admit`).

        Legged admission (prefix cache / chunked prefill) builds a
        `_PrefillJob`; with ``drain`` (default — external drivers like the
        multi-tenant server need admission to complete before the clock
        moves elsewhere) every leg runs before this returns, otherwise the
        job queues on ``sess.jobs`` and `serve()` advances one leg per loop
        iteration, interleaved with decode chunks."""
        with tracing.span("admit", rid=req.rid):
            report = sess.report
            rec = RequestRecord(request=req, t_admit=now)
            report.records[req.rid] = rec
            if req.max_new > 1 and (self._use_legs or self._legs_rec):
                return self._admit_legged(sess, req, rec, now, drain)
            # ---- dense prefill (also paged mode A: dense prefill, paged
            # insert) ----------------------------------------------------
            # with no EOS configured, NOTHING about admission depends on
            # the first token's value — defer the host read to the next
            # chunk sync so admission overlaps the in-flight chunk instead
            # of waiting behind it on the device queue
            lazy = self.eos_id is None
            tok1, cache1, first, dt = self._prefill_request(req, rec, lazy)
            now += dt
            report.wall_prefill_s += dt
            report.n_prefills += 1
            report.prefill_pad_vectors += rec.pad_vectors
            report.observed_vectors += len(req.prompt)
            rec.t_first = now
            if lazy:
                sess.lazy.append((rec, tok1))
            else:
                eos_hit = first == self.eos_id
                if not eos_hit:
                    # the EOS token is control, not payload: it never lands in
                    # `rec.tokens` (so generated_tokens / tok_s count delivered
                    # output only), but its vector stays in the CM_* books
                    rec.tokens.append(first)
                if eos_hit:
                    self._retire(rec, "eos", now)
                    return now
            if req.max_new == 1:
                self._retire(rec, "length", now)
                return now
            slot = sess.slots.alloc(req.rid)
            sess.slot_rec[slot] = rec
            rem = req.max_new - 1
            if not self.recurrent:
                rem = min(rem, self.max_seq - len(req.prompt))
            sess.rem[slot] = rem
            t0 = time.perf_counter()
            with tracing.span("insert", rid=req.rid, slot=slot):
                if self._paged_kv:
                    pt_row, mask, owned = self._alloc_pt_row(req)
                    sess.slot_pages[slot] = (owned, [])
                    (sess.cache, sess.tok_buf,
                     sess.state) = self._jit_insert_paged(
                        sess.cache, cache1, sess.tok_buf, tok1, sess.state,
                        jnp.int32(slot), jnp.int32(len(req.prompt)),
                        jnp.int32(req.max_new), pt_row, mask)
                else:
                    sess.cache, sess.tok_buf, sess.state = self._jit_insert(
                        sess.cache, cache1, sess.tok_buf, tok1, sess.state,
                        jnp.int32(slot), jnp.int32(len(req.prompt)),
                        jnp.int32(req.max_new))
            if not lazy:
                # the blocking (EOS-aware) path bills the full prefill+insert
                # wall here; the lazy path bills dispatch only — the device
                # time lands in the next chunk's window, where it actually
                # serializes (insert chains on the in-flight chunk's outputs)
                sess.tok_buf.block_until_ready()
            ins = time.perf_counter() - t0
            now += ins
            report.wall_prefill_s += ins
            return now

    # -- paged admission (DESIGN.md §15) -------------------------------------
    def _pages_span(self, req: Request) -> tuple[int, int]:
        """(last row index + 1 this request can ever write, pages that
        cover it). Decode budget caps growth: rem is clipped at admission,
        so rows past ``end`` are never written OR read."""
        plen = len(req.prompt)
        end = plen + min(req.max_new - 1, self.max_seq - plen)
        return end, -(-end // self.page_size)

    def _alloc_pages(self, n: int, owner, protect=()):
        """``n`` pages, evicting sole-sharer prefix entries (LRU) to make
        room. Raises on a genuine shortage — `can_admit` gates callers."""
        pids = self.pages.alloc(n, owner=owner)
        if pids is None and self.prefix is not None:
            self.prefix.evict(n - self.pages.n_free, protect=protect)
            pids = self.pages.alloc(n, owner=owner)
        if pids is None:
            raise RuntimeError(
                f"page pool exhausted: {owner} needs {n} pages, "
                f"{self.pages.n_free} free (gate admission on can_admit)")
        return pids

    def _alloc_pt_row(self, req: Request):
        """Mode-A page grab: all pages owned, no sharing."""
        from repro.runtime.pages import SCRATCH
        _, n_alloc = self._pages_span(req)
        owned = self._alloc_pages(n_alloc, req.rid)
        row = owned + [SCRATCH] * (self._pt_width - n_alloc)
        mask = [j < n_alloc for j in range(self._pt_width)]
        return (jnp.asarray(row, jnp.int32), jnp.asarray(mask, bool), owned)

    def _peek_prefix(self, prompt) -> tuple[int, list]:
        """(f_eff, hit pids) a transformer admission WOULD reuse: the
        longest consecutive run of resident full pages, capped so the
        continuation keeps >= 1 token. Non-perturbing (LRU/stats untouched)
        — `can_admit` probes feasibility without committing."""
        if self.prefix is None or not self._use_legs:
            return 0, []
        from repro.runtime.pages import page_keys
        got = self.prefix.lookup(page_keys(prompt, self.page_size),
                                 peek=True)
        f = 0
        while f < len(got) and got[f] is not None:
            f += 1
        f = min(f, (len(prompt) - 1) // self.page_size)
        return f, got[:f]

    def pages_needed(self, req: Request) -> int:
        """Pages an admission would NEWLY allocate — the tenant-quota unit
        (shared prefix pages are not billed to their sharers)."""
        if not self._paged_kv or req.max_new <= 1:
            return 0
        _, n_alloc = self._pages_span(req)
        f_eff, _ = self._peek_prefix(req.prompt)
        return n_alloc - f_eff

    def can_admit(self, sess: "EngineSession", req: Request) -> bool:
        """Whether the page pool can cover ``req`` right now (free pages
        plus cache-only entries admission may evict, minus the hit pages
        about to gain a sharer — those must not count as reclaimable).
        Unpaged / recurrent engines always admit: snapshot pages are
        best-effort, never required."""
        if not self._paged_kv or req.max_new <= 1:
            return True
        _, n_alloc = self._pages_span(req)
        f_eff, hit_pids = self._peek_prefix(req.prompt)
        have = self.pages.n_free
        if self.prefix is not None:
            have += self.prefix.evictable(protect=hit_pids)
        return have >= n_alloc - f_eff

    def tenant_pages(self, sess: "EngineSession",
                     tenant_of: dict | None = None) -> dict:
        """tenant -> pages currently held as owner across busy slots (the
        quota view `runtime.server` charges against). ``tenant_of`` maps
        rid -> tenant (the server's view); without it requests fall under
        one anonymous tenant."""
        held: dict = {}
        for slot, (owned, _shared) in sess.slot_pages.items():
            rec = sess.slot_rec.get(slot)
            if rec is None:
                continue
            t = (tenant_of.get(rec.request.rid) if tenant_of is not None
                 else None)
            held[t] = held.get(t, 0) + len(owned)
        return held

    def _admit_legged(self, sess: "EngineSession", req: Request,
                      rec: RequestRecord, now: float, drain: bool) -> float:
        """Admission via prefill legs: look up the shared prefix, allocate
        the continuation's pages (transformer) or restore the deepest
        snapshot (recurrent), split the rest of the prompt into legs, and
        run them (now, or interleaved — see `admit`)."""
        from repro.runtime.pages import SCRATCH, page_keys
        report = sess.report
        p, c = self.page_size, self._leg_c
        prompt = list(req.prompt)
        plen = len(prompt)
        if plen > self.prompt_pad:
            raise ValueError(f"prompt length {plen} exceeds "
                             f"prompt_pad {self.prompt_pad}")
        keys = page_keys(prompt, p) if self.prefix is not None else []
        start, f_eff, pids_hit, carry, pt_row, owned = 0, 0, [], None, None, []
        if self._use_legs:
            if self.prefix is not None:
                got = self.prefix.lookup(keys)
                while f_eff < len(got) and got[f_eff] is not None:
                    f_eff += 1
                # cap so the continuation keeps >= 1 real token (the legs
                # must produce the first-token logits)
                f_eff = min(f_eff, (plen - 1) // p)
                pids_hit = [got[j] for j in range(f_eff)]
                for pid in pids_hit:
                    self.pages.retain(pid)     # sharer refs FIRST: eviction
                                               # below must not free them
            start = f_eff * p
            _, n_alloc = self._pages_span(req)
            try:
                owned = self._alloc_pages(n_alloc - f_eff, req.rid,
                                          protect=pids_hit)
            except RuntimeError:
                for pid in pids_hit:
                    self.pages.release(pid)
                raise
            row = pids_hit + owned + [SCRATCH] * (self._pt_width - n_alloc)
            pt_row = jnp.asarray(row, jnp.int32)
        else:
            # recurrent: ONE snapshot page restores the whole state at a
            # page boundary — take the deepest resident one
            hit_j = -1
            if self.prefix is not None:
                got = self.prefix.lookup(keys)
                for j in range(min(len(got), (plen - 1) // p)):
                    if got[j] is not None:
                        hit_j = j
            if hit_j >= 0:
                carry = self._jit_snap_get(self._snap_pool(),
                                           jnp.int32(got[hit_j]))
                start = (hit_j + 1) * p
                f_eff = hit_j + 1
            else:
                carry = self._commit_ambient(self.model.init_cache(
                    self.cfg, 1, self.max_seq, self.cache_dtype))
        legs, pos = [], start
        while pos < plen:
            span = min(c, plen - pos)
            toks = prompt[pos:pos + span] + [self.pad_id] * (c - span)
            legs.append((pos, span, jnp.asarray(toks, jnp.int32)[None]))
            pos += span
        report.n_prefills += 1
        if start:
            report.prefix_hits += 1
            report.prefix_hit_vectors += start
        slot = sess.slots.alloc(req.rid)
        sess.slot_rec[slot] = rec
        if self._use_legs:
            sess.slot_pages[slot] = (owned, pids_hit)
        job = _PrefillJob(req=req, rec=rec, slot=slot, legs=legs,
                          pt_row=pt_row, keys=keys, f_eff=f_eff, carry=carry)
        if drain or not self._chunked:
            while not job.done:
                now = self._advance_leg(sess, job, now)
        else:
            sess.jobs.append(job)
        return now

    def _advance_leg(self, sess: "EngineSession", job: _PrefillJob,
                     now: float) -> float:
        """Run ONE prefill leg; finalize the job after its last. Vector
        books advance per leg (not at admission) so an aborted job's record
        matches exactly what the device observed."""
        report = sess.report
        pos0, span, toks = job.legs[job.leg_i]
        t0 = time.perf_counter()
        if self._use_legs:
            tok1, kp, vp = self._jit_leg(
                self.params, toks, sess.cache["kp"], sess.cache["vp"],
                job.pt_row, jnp.int32(pos0), jnp.int32(span))
            sess.cache["kp"], sess.cache["vp"] = kp, vp
        else:
            tok1, job.carry = self._jit_leg_rec(
                self.params, job.carry, toks, jnp.int32(span))
        tok1.block_until_ready()
        dt = time.perf_counter() - t0
        now += dt
        report.wall_prefill_s += dt
        report.observed_vectors += span
        report.prefill_chunks += 1
        rec = job.rec
        rec.prefill_vectors += span
        rec.pad_vectors += self._leg_c - span
        report.prefill_pad_vectors += self._leg_c - span
        job.tok1 = tok1
        job.leg_i += 1
        end = pos0 + span
        if self._snap and end % self.page_size == 0:
            self._register_snapshot(job, end)
        if job.done:
            now = self._finalize_job(sess, job, now)
        return now

    def _register_snapshot(self, job: _PrefillJob, end: int):
        """Store the carried recurrent state at a page-aligned leg end.
        Best effort: an exhausted pool skips the snapshot, never the
        request — restores stay instantaneous (no retain on hit needed;
        fresh entries are LRU-protected by their put tick)."""
        key = job.keys[end // self.page_size - 1]
        if key in self.prefix:
            return
        pids = self.pages.alloc(1, owner=("snap", job.req.rid))
        if pids is None and self.prefix.evict(1):
            pids = self.pages.alloc(1, owner=("snap", job.req.rid))
        if pids is None:
            return
        self._pool_snap = self._jit_snap_put(self._snap_pool(), job.carry,
                                             jnp.int32(pids[0]))
        self.prefix.put(key, pids[0], adopt=True)

    def _finalize_job(self, sess: "EngineSession", job: _PrefillJob,
                      now: float) -> float:
        """Last leg done: register produced prefix pages, deliver/inspect
        the first token, and arm the decode lane."""
        report = sess.report
        req, rec, tok1 = job.req, job.rec, job.tok1
        plen = len(req.prompt)
        if self._use_legs and self.prefix is not None:
            # register this prompt's produced FULL pages: the cache takes
            # one ref ON TOP of the producer's (released at retire), so the
            # pages outlive the request. First producer wins; a racing
            # duplicate's page stays request-owned and frees at retire.
            pids_all = list(sess.slot_pages[job.slot][1]) + \
                list(sess.slot_pages[job.slot][0])
            for j in range(job.f_eff, plen // self.page_size):
                self.prefix.put(job.keys[j], pids_all[j])
        rec.t_first = now
        if self.eos_id is None:
            sess.lazy.append((rec, tok1))
        else:
            first = int(tok1[0, 0])
            if first == self.eos_id:
                self._retire(rec, "eos", now)
                self._free_slot(sess, job.slot)
                return now
            rec.tokens.append(first)
        t0 = time.perf_counter()
        if self._use_legs:
            (sess.cache["pt"], sess.cache["len"], sess.tok_buf,
             sess.state) = self._jit_register(
                sess.cache["pt"], sess.cache["len"], sess.tok_buf, tok1,
                sess.state, jnp.int32(job.slot), jnp.int32(plen),
                jnp.int32(req.max_new), job.pt_row)
        else:
            sess.cache, sess.tok_buf, sess.state = self._jit_insert(
                sess.cache, job.carry, sess.tok_buf, tok1, sess.state,
                jnp.int32(job.slot), jnp.int32(plen),
                jnp.int32(req.max_new))
        dt = time.perf_counter() - t0
        now += dt
        report.wall_prefill_s += dt
        rem = req.max_new - 1
        if not self.recurrent:
            rem = min(rem, self.max_seq - plen)
        sess.rem[job.slot] = rem
        return now

    def _advance_prefill(self, sess: "EngineSession", now: float) -> float:
        """Advance ONE leg of the oldest queued prefill job — the loop-
        cadence unit that interleaves long prefills with decode chunks."""
        job = sess.jobs[0]
        now = self._advance_leg(sess, job, now)
        if job.done:
            sess.jobs.pop(0)
        return now

    def _release_slot_pages(self, sess: "EngineSession", slot: int):
        """Drop the retiring slot's page refs (owned allocations AND the
        sharer refs its prefix hits took). Cache-registered pages survive
        through the cache's own reference."""
        held = sess.slot_pages.pop(slot, None)
        if held is None:
            return
        owned, shared = held
        for pid in list(owned) + list(shared):
            self.pages.release(pid)

    def _free_slot(self, sess: "EngineSession", slot: int):
        sess.slot_rec.pop(slot, None)
        sess.slots.release(slot)
        sess.rem.pop(slot, None)
        self._release_slot_pages(sess, slot)

    def _pick_chunk(self, sess: "EngineSession",
                    responsive: bool = False) -> int:
        """Chunk length for the next dispatch, from the compiled ladder.

        Default: the largest ladder length not exceeding the longest
        PROJECTED remaining budget across busy lanes — maximum host-round
        amortization, and a chunk never runs past the last projected-live
        lane. ``responsive`` (requests are waiting for a slot): the
        SMALLEST ladder length covering the earliest projected retirement,
        so the freed slot reaches the admission loop promptly instead of
        idling to the end of a long chunk. 0 means every in-flight lane is
        projected retired (a dispatch would scan an all-frozen batch —
        skip it)."""
        rems = [r for r in (sess.rem.get(s, 0) for s in sess.slot_rec)
                if r > 0]
        if not rems:
            return 0
        if responsive:
            target = min(rems)
            for n in self._ladder:
                if n >= target:
                    return n
            return self._ladder[-1]
        target = max(rems)
        for n in reversed(self._ladder):
            if n <= target:
                return n
        return 1

    def _dispatch_chunk(self, sess: "EngineSession",
                        n: int | None = None) -> _PendingChunk:
        """Launch one ``n``-step scan (a compiled ladder length, default
        host-picked) WITHOUT waiting for its results; `sess`'s device
        buffers advance to the chunk's outputs so the next chunk (or an
        insert) chains on-device."""
        if n is None:
            n = self._pick_chunk(sess) or 1
        t0 = time.perf_counter()
        with tracing.span("dispatch", n=n):
            sess.tok_buf, sess.cache, sess.state, ys = self._safe_decodes[n](
                self.params, sess.cache, sess.tok_buf, sess.state)
        for slot in sess.slot_rec:
            sess.rem[slot] = max(0, sess.rem.get(slot, 0) - n)
        self._chunks_dispatched += 1
        return _PendingChunk(ys=ys, t_wall=t0,
                             prefill0=sess.report.wall_prefill_s, n=n,
                             health0=sess.report.wall_health_s,
                             recals0=sess.report.n_recals,
                             swap0=sess.report.wall_swap_s,
                             swaps0=sess.report.n_swaps)

    def _process_chunk(self, sess: "EngineSession", pend: _PendingChunk,
                       now: float) -> float:
        """Sync one dispatched chunk and mirror its on-device retirement
        rows into the host books. Billing: the chunk costs (wall since
        dispatch) minus any prefill/insert wall already billed inside that
        window — the double-buffered loop admits WHILE a chunk flies."""
        with tracing.span("sync"):
            toks, acts, reasons, *touched = jax.device_get(pend.ys)
        # any admission since the last sync has its prefill long done by
        # now (the chunk we just read back queued after it) — the deferred
        # first-token reads cost a host copy, not a wait
        self._resolve_firsts(sess)
        with tracing.span("books"):
            report = sess.report
            overlap = ((report.wall_prefill_s - pend.prefill0)
                       + (report.wall_health_s - pend.health0)
                       + (report.wall_swap_s - pend.swap0))
            dt = max(time.perf_counter() - pend.t_wall - overlap, 0.0)
            now += dt
            report.wall_decode_s += dt
            ran = int(toks.shape[0])
            busy = int(acts.sum())
            report.n_steps += ran
            # busy-lane counts come from the DEVICE (chunk ys), independent of
            # the per-request records — reconcile compares two real countings
            report.observed_vectors += busy
            report.idle_vectors += self.n_slots * ran - busy
            if touched:
                report.experts_touched += int(touched[0].sum())
            self._step_no += ran
            # a chunk whose window held a hot reprogram is legitimately slow:
            # exempt it from the straggler EWMA (flagging recovery would page
            # an operator for behavior the engine itself caused, and the
            # inflated sample would poison the baseline)
            self.monitor.record(self._step_no, dt / max(ran, 1),
                                exempt=(report.n_recals > pend.recals0
                                        or report.n_swaps > pend.swaps0))
            if self.heartbeat is not None:
                self.heartbeat.beat(
                    self._step_no, slots_busy=sess.slots.n_busy,
                    slots_free=sess.slots.n_free, chunk_len=ran,
                    last_chunk_s=time.time(),
                    wall_decode_s=report.wall_decode_s,
                    n_recals=report.n_recals)

            for s in range(ran):
                for slot in list(sess.slot_rec):
                    if not acts[s, slot]:
                        continue    # freed/refilled after the dispatch
                    rec = sess.slot_rec[slot]
                    rec.decode_vectors += 1
                    r = int(reasons[s, slot])
                    if r != 2:      # EOS is control, not payload (see admit)
                        rec.tokens.append(int(toks[s, slot]))
                    if r:
                        self._retire(rec, _REASONS[r], now)
                        sess.slot_rec.pop(slot)
                        sess.slots.release(slot)
                        sess.rem.pop(slot, None)
                        self._release_slot_pages(sess, slot)
            return now

    @staticmethod
    def _resolve_firsts(sess: "EngineSession"):
        """Read back the deferred prefill first-tokens (lazy admission,
        `admit`). Runs before any decode-token append for those records —
        a record admitted after a chunk's dispatch shows acts=False for
        that whole chunk, so its first token always lands at index 0."""
        for rec, tok1 in sess.lazy:
            with tracing.span("first_token", rid=rec.request.rid):
                rec.tokens.insert(0, int(tok1[0, 0]))
        sess.lazy.clear()

    def step(self, sess: "EngineSession", now: float) -> float:
        """One SYNCHRONOUS decode chunk (``decode_chunk`` dense steps,
        dispatched and immediately processed) + retirement bookkeeping;
        returns the advanced clock. Caller guarantees ``sess.slots.n_busy
        > 0``. External drivers (the multi-tenant server) see retirement
        and quota accounting land on chunk boundaries; `serve()` instead
        double-buffers dispatch/process for comm/compute overlap."""
        now = self._resilience_tick(sess, now)
        now = self._placement_tick(sess, now)
        return self._process_chunk(sess, self._dispatch_chunk(sess), now)

    def cancel_active(self, sess: "EngineSession", now: float):
        """Retire every in-flight request with reason "cap" (step budget).
        The device-side active rows are left stale on purpose — a canceled
        session is never stepped again."""
        self._resolve_firsts(sess)
        for job in sess.jobs:    # half-prefilled requests lose their slot
            self._retire(job.rec, "cap", now)
            self._free_slot(sess, job.slot)
        sess.jobs.clear()
        for slot in list(sess.slot_rec):
            self._retire(sess.slot_rec.pop(slot), "cap", now)
            sess.slots.release(slot)
            sess.rem.pop(slot, None)
            self._release_slot_pages(sess, slot)

    def finish(self, sess: "EngineSession", now: float) -> ServeReport:
        """Close the session and return its report. Paged engines hand the
        pool buffers back to the engine (prefix pages stay resident for the
        next session) and snapshot the allocator's exact-partition ledger."""
        self._resolve_firsts(sess)
        report = sess.report
        for job in list(sess.jobs):   # a closed session holds nothing
            self._retire(job.rec, "cap", now)
            self._free_slot(sess, job.slot)
        sess.jobs.clear()
        if self._paged_kv:
            self._pool = (sess.cache["kp"], sess.cache["vp"])
        if self.pages is not None:
            report.page_ledger = self.pages.ledger()
            report.page_ledger_exact = self.pages.verify()
        if self.prefix is not None:
            report.page_evictions = self.prefix.evictions - sess.evictions0
            report.prefix_stats = self.prefix.stats()
        report.makespan_s = now
        report.retries = self._retries - sess.retries0
        report.stragglers = list(self.monitor.flagged[sess.flagged0:])
        return report

    # -- the serving loop ----------------------------------------------------
    def serve(self, requests, max_steps: int = 100_000) -> ServeReport:
        """Serve a full trace to completion (simulated arrival clock).

        The engine clock starts at 0 and advances by the measured wall time
        of each device call; when every slot is empty it jumps to the next
        arrival. Request arrival times are in the same (second) units.

        Decode is DOUBLE-BUFFERED: chunk i+1 is dispatched before chunk
        i's token block is read back, so host bookkeeping and admission
        overlap device compute. Per-request tokens are unaffected — decode
        lanes are row-independent, so what a request generates never
        depends on which chunk (or which lane-mates) it rode with."""
        queue = Batcher(requests, policy=self.admission)
        sess = self.begin()
        now = 0.0
        pending: _PendingChunk | None = None

        while len(queue) or sess.slots.n_busy or pending is not None:
            # ---- admission + slot refill (continuous batching) ------------
            while sess.slots.n_free:
                if self._paged_kv:
                    # ask the allocator BEFORE popping: a request that does
                    # not fit waits at the head (order-preserving HOL block;
                    # never deadlocks — an all-free engine can always cover
                    # one max-length request, ctor-checked)
                    req = queue.peek_ready(now)
                    if req is None or not self.can_admit(sess, req):
                        break
                    queue.pop_ready(now)
                else:
                    req = queue.pop_ready(now)
                    if req is None:
                        break
                now = self.admit(sess, req, now, drain=not self._chunked)

            # ---- chunked-prefill legs ride the loop cadence ---------------
            if sess.jobs:
                now = self._advance_prefill(sess, now)

            # ---- chunk-boundary resilience (drift / chaos / recal) ---------
            now = self._resilience_tick(sess, now)

            # ---- chunk-boundary rotation swap (capacity overflow) ----------
            now = self._placement_tick(sess, now)

            if not sess.slots.n_busy and pending is None:
                nxt = queue.next_arrival()
                if nxt is None:
                    break
                now = max(now, nxt)       # idle: jump to the next arrival
                continue

            # ---- one decode chunk, double-buffered ------------------------
            in_flight = pending.n if pending is not None else 0
            capped = sess.report.n_steps + in_flight >= max_steps
            n_next = (self._pick_chunk(sess, responsive=bool(len(queue)))
                      if sess.slots.n_busy else 0)
            cur = (self._dispatch_chunk(sess, n_next)
                   if n_next and not capped else None)
            if pending is not None:
                now = self._process_chunk(sess, pending, now)
            pending = cur
            if capped and pending is None:
                self.cancel_active(sess, now)
                break

        return self.finish(sess, now)

    # -- CM_* books ----------------------------------------------------------
    def ledgers(self, report: ServeReport) -> dict:
        """rid -> CM_* counts (requires a programmed engine)."""
        from repro.runtime.batcher import request_ledgers
        if self.program is None:
            raise ValueError("CM_* ledgers require an AimcProgram")
        if self.rotation is not None:
            raise ValueError(
                "per-request CM_* ledgers are ill-defined under rotation: "
                "a request's vectors span states with different analog "
                "sets; use report.swap_events + placement.reconcile_swaps")
        return request_ledgers(self.program, report.records)

    def core_ledgers(self, report: ServeReport) -> dict:
        """core -> CM_* totals for this run's useful vectors (requires a
        `CoreSchedule`). The per-core split of `ledgers`: summed over cores
        the dequeue/initialize books close exactly against
        ``program.mvm_counts()`` (`batcher.reconcile_cores`)."""
        from repro.runtime.batcher import aggregate_core_ledgers
        if self.schedule is None:
            raise ValueError("per-core ledgers require a CoreSchedule")
        return aggregate_core_ledgers(self.schedule, report.records)


def _on_mesh(method):
    """An engine entry point run under ``jax.set_mesh(self.mesh)``: a
    compiled kernel maps itself over the ambient mesh (GSPMD cannot
    partition a Mosaic call; kernels/ops.py), and the closures' executable
    caches key on it."""
    @functools.wraps(method)
    def run(self, *args, **kw):
        with jax.set_mesh(self.mesh):
            return method(self, *args, **kw)
    return run


class ShardedServeEngine(ServeEngine):
    """`ServeEngine` with its device state laid out over a real JAX mesh.

    The multi-device join of the three prior subsystems (DESIGN.md §11):
    the installed `AimcProgram`'s crossbar states column-shard their bit
    lines over the mesh's ``model`` axis (`shardings.serve_engine_param_
    specs` — the layout `core.schedule` proves exact), every digital leaf
    replicates over ``data`` (weights-stationary serving), and the decode
    slots — KV caches, recurrent state, the token buffer, the retirement
    state rows — shard over the data axes so each data-parallel device
    advances its own lanes. All three closures are compiled ONCE with `NamedSharding`-pinned
    inputs AND outputs, so the cache lives sharded on-device across the
    whole serving session; the host-side loop (admission, slots,
    accounting) is inherited unchanged.

    Correctness bar: no reduction dimension is ever sharded — column splits
    concatenate and batch rows are independent — so decode output is
    BIT-EQUAL to the single-device `ServeEngine` on the same trace
    (tests/test_sharded_engine.py, forced 2-device host-platform mesh).

    When a `CoreSchedule` is attached, `schedule.mesh_placement` maps its
    virtual cores onto the model-axis devices and `device_ledgers` reports
    CM_* totals per mesh device; per-request ledgers aggregate across
    shards exactly as the single-core path (`batcher.reconcile_cores`).

    ``n_slots`` should divide the data-axis size (and crossbar Np the
    model-axis size) for the sharding to take effect; non-dividing
    dimensions fall back to replicated rather than failing.
    """

    def __init__(self, model, cfg, exe: Execution, params, *, mesh,
                 model_axis: str = "model", **kw):
        if kw.get("rotation") is not None:
            raise ValueError(
                "ShardedServeEngine does not serve rotation plans: state "
                "swaps would re-place every parameter tree on the mesh "
                "mid-trace (use the single-device engine for overflow)")
        self.mesh = mesh
        self.model_axis = model_axis
        super().__init__(model, cfg, exe, params, **kw)

    # every public entry point that reaches a compiled closure
    warmup = _on_mesh(ServeEngine.warmup)
    admit = _on_mesh(ServeEngine.admit)
    step = _on_mesh(ServeEngine.step)
    serve = _on_mesh(ServeEngine.serve)

    def _build_closures(self, max_retries: int):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import dp_axes
        from repro.launch.shardings import (fit_spec, serve_engine_param_specs,
                                            slot_cache_specs, slot_state_specs,
                                            to_named)
        mesh = self.mesh

        def named_replicated(shape_tree):
            return jax.tree.map(
                lambda l: NamedSharding(mesh, P(*([None] * l.ndim))),
                shape_tree)

        params_shape = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params)
        pspecs = serve_engine_param_specs(params_shape, mesh, self.model_axis)
        self._param_sh = to_named(pspecs, mesh)
        # place the (installed) tree once, outside the serving clock
        self.params = jax.device_put(self.params, self._param_sh)

        if self._paged_kv:
            # paged cache dict: pools split at the PAGE axis over data (a
            # page never splits across the reduction dim — heads/rows stay
            # whole), table + lengths at the slot axis like the dense state
            pool_shape = jax.eval_shape(lambda: self.model.init_paged_cache(
                self.cfg, self.pages.n_pages, self.page_size,
                self.cache_dtype))
            cache_shape = {
                "kp": pool_shape["kp"], "vp": pool_shape["vp"],
                "pt": jax.ShapeDtypeStruct(
                    (self.n_slots, self._pt_width), jnp.int32),
                "len": jax.ShapeDtypeStruct((self.n_slots,), jnp.int32)}
            self._cache_sh = to_named(
                slot_cache_specs(cache_shape, self._paged_axes(), mesh),
                mesh)
        else:
            cache_shape = jax.eval_shape(lambda: self.model.init_cache(
                self.cfg, self.n_slots, self.max_seq, self.cache_dtype))
            self._cache_sh = to_named(
                slot_cache_specs(cache_shape, self._axes, mesh), mesh)
        dp = dp_axes(mesh)
        tok_sh = NamedSharding(
            mesh, fit_spec(P(dp, None), (self.n_slots, 1), mesh))
        self._tok_sh = tok_sh
        state_shape = jax.eval_shape(lambda: ServeEngine._empty_state(self))
        self._state_sh = to_named(slot_state_specs(state_shape, mesh), mesh)
        repl = NamedSharding(mesh, P())   # fully replicated, any rank
        # chunk outputs: per-step [n, n_slots] rows follow the lane split
        # (slots over data axes); the spec is shape-free, so one sharding
        # serves every compiled ladder length
        ys_row = NamedSharding(mesh, fit_spec(
            P(None, dp), (self.decode_chunk, self.n_slots), mesh))
        ys_sh = (ys_row, ys_row, ys_row)
        if self._count_experts:
            ys_sh += (repl,)              # the experts touched per step

        tokens_s = jax.ShapeDtypeStruct((1, self.prompt_pad), jnp.int32)
        vl_s = jax.ShapeDtypeStruct((1,), jnp.int32)
        cache1_shape = jax.eval_shape(self._prefill_fn, params_shape,
                                      tokens_s, vl_s)[1]
        cache1_sh = named_replicated(cache1_shape)   # [1, ...]: nothing to split

        self._jit_prefill = jax.jit(
            self._traced(self._prefill_fn),
            in_shardings=(self._param_sh, repl, repl),
            out_shardings=(repl, cache1_sh))
        if self._paged_kv:
            # dense insert is unused when paged, but keep it compiled
            # against the DENSE cache layout for API parity
            dense_shape = jax.eval_shape(lambda: self.model.init_cache(
                self.cfg, self.n_slots, self.max_seq, self.cache_dtype))
            dense_sh = to_named(
                slot_cache_specs(dense_shape, self._axes, mesh), mesh)
        else:
            dense_sh = self._cache_sh
        self._jit_insert = jax.jit(
            self._insert_fn, donate_argnums=(0, 2, 4),
            in_shardings=(dense_sh, cache1_sh, tok_sh, repl,
                          self._state_sh, repl, repl, repl),
            out_shardings=(dense_sh, tok_sh, self._state_sh))
        decode_fn = self._decode_fn
        if self._paged_kv:
            decode_fn = self._decode_paged_fn
            self._jit_insert_paged = jax.jit(
                self._insert_paged_fn, donate_argnums=(0, 2, 4),
                in_shardings=(self._cache_sh, cache1_sh, tok_sh, repl,
                              self._state_sh, repl, repl, repl, repl, repl),
                out_shardings=(self._cache_sh, tok_sh, self._state_sh))
        if self._use_legs:
            kp_sh, vp_sh = self._cache_sh["kp"], self._cache_sh["vp"]
            self._jit_leg = jax.jit(
                self._traced(self._leg_fn), donate_argnums=(2, 3),
                in_shardings=(self._param_sh, repl, kp_sh, vp_sh, repl,
                              repl, repl),
                out_shardings=(repl, kp_sh, vp_sh))
            self._jit_register = jax.jit(
                self._register_fn, donate_argnums=(0, 1, 2, 4),
                in_shardings=(self._cache_sh["pt"], self._cache_sh["len"],
                              tok_sh, repl, self._state_sh, repl, repl,
                              repl, repl),
                out_shardings=(self._cache_sh["pt"], self._cache_sh["len"],
                               tok_sh, self._state_sh))
        if self._legs_rec:
            # pin the carried [1, ...] state replicated: snap_get outputs
            # and fresh init_cache trees must key ONE executable each
            self._jit_leg_rec = jax.jit(
                self._traced(self._leg_rec_fn), donate_argnums=(1,),
                in_shardings=(self._param_sh, cache1_sh, repl, repl),
                out_shardings=(repl, cache1_sh))
        if self._snap:
            pool_shape = jax.eval_shape(lambda: self.model.init_cache(
                self.cfg, self.pages.n_pages, self.max_seq,
                self.cache_dtype))
            pool_sh = named_replicated(pool_shape)
            self._jit_snap_put = jax.jit(
                self._snap_put_fn, donate_argnums=(0,),
                in_shardings=(pool_sh, cache1_sh, repl),
                out_shardings=pool_sh)
            self._jit_snap_get = jax.jit(
                self._snap_get_fn, in_shardings=(pool_sh, repl),
                out_shardings=cache1_sh)
        self._decode_jits = {
            n: jax.jit(
                self._traced(functools.partial(decode_fn, length=n)),
                in_shardings=(self._param_sh, self._cache_sh, tok_sh,
                              self._state_sh),
                out_shardings=(tok_sh, self._cache_sh, self._state_sh,
                               ys_sh))
            for n in self._ladder}
        self._safe_decodes = {
            n: resilient_step(f, max_retries=max_retries,
                              on_retry=lambda attempt, e: self._count_retry())
            for n, f in self._decode_jits.items()}

    def _set_params(self, params):
        # re-pin the updated tree to the mesh layout the closures were
        # compiled against (identical treedef/shapes -> no recompile)
        self.params = jax.device_put(params, self._param_sh)

    def _fresh_pools(self):
        pools = self.model.init_paged_cache(
            self.cfg, self.pages.n_pages, self.page_size, self.cache_dtype,
            shardings={"kp": self._cache_sh["kp"],
                       "vp": self._cache_sh["vp"]})
        return pools["kp"], pools["vp"]

    def _paged_cache_dict(self, kp, vp):
        # pools were placed by _fresh_pools; commit table + lengths
        cache = ServeEngine._paged_cache_dict(self, kp, vp)
        cache["pt"] = jax.device_put(cache["pt"], self._cache_sh["pt"])
        cache["len"] = jax.device_put(cache["len"], self._cache_sh["len"])
        return cache

    def _empty_cache(self):
        if self._paged_kv:
            return ServeEngine._empty_cache(self)
        # created ON the mesh placement (models' sharding-annotated init)
        return self.model.init_cache(self.cfg, self.n_slots, self.max_seq,
                                     self.cache_dtype,
                                     shardings=self._cache_sh)

    def _empty_tok_buf(self):
        return jax.device_put(super()._empty_tok_buf(), self._tok_sh)

    def _empty_state(self):
        return jax.device_put(super()._empty_state(), self._state_sh)

    def device_ledgers(self, report: ServeReport) -> dict:
        """model-axis device slot -> CM_* totals for this run, through the
        schedule's core->device placement (`CoreSchedule.mesh_placement`)."""
        if self.schedule is None:
            raise ValueError("device ledgers require a CoreSchedule")
        n_vec = report.useful_vectors
        return {dev: led.cm.scaled(n_vec)
                for dev, led in self.schedule.device_ledgers(
                    self.mesh, self.model_axis).items()}


# ---------------------------------------------------------------------------
# the legacy static-batch path (A/B baseline + bit-equality oracle)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def _static_closures(model, cfg, exe, max_seq, cache_dtype):
    """Jitted static-path closures, cached per configuration — a fresh
    `jax.jit(lambda ...)` per call would recompile every invocation and
    bill the A/B baseline for jit time the engine's warmup doesn't pay."""
    prefill = jax.jit(at_stated_precision(lambda pr, tk: model.prefill(
        pr, tk, cfg, exe, max_seq=max_seq, cache_dtype=cache_dtype), exe))
    decode = jax.jit(at_stated_precision(
        lambda pr, ca, tk: model.decode_step(pr, ca, tk, cfg, exe), exe))
    return prefill, decode


def static_generate(model, cfg, exe: Execution, params, prompts, gen: int,
                    max_seq: int | None = None, cache_dtype=jnp.float32):
    """The monolithic serve loop this engine replaced: one synchronized
    batch, one prompt length, ``gen`` lockstep decode steps. Kept as the
    oracle the continuous-batching tests compare against bit-for-bit, and
    as the bench's static-batching baseline.

    prompts: [B, P] int32. Returns ([B, gen] tokens, wall seconds
    (prefill_s, decode_s)). ``gen=1`` is prefill-only: no decode loop runs
    and the decode time is honestly 0.0.
    """
    b, p = prompts.shape
    max_seq = max_seq or (p + gen)
    prefill, decode = _static_closures(model, cfg, exe, max_seq, cache_dtype)
    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts)
    out = [jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]]
    jax.block_until_ready(out[-1])
    t_prefill = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(gen - 1):
        logits, cache = decode(params, cache, out[-1])
        out.append(jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None])
    if gen > 1:
        jax.block_until_ready(out[-1])
    t_decode = time.perf_counter() - t0 if gen > 1 else 0.0
    return jnp.concatenate(out, axis=1), (t_prefill, t_decode)
