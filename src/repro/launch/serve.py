"""Serving CLI — a thin front door over the continuous-batching engine
(`runtime.engine.ServeEngine`).

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
        --requests 8 --prompt-len 16 --gen 12 [--exec aimc] [--int8] \
        [--trace poisson:200] [--slots 4] [--static]

The paper's deployment model made literal (§IV-B, Fig. 4): with ``--exec
aimc`` the whole network is programmed ONCE (CM_INITIALIZE, outside the
region of interest), the `AimcProgram` is install()ed into the parameter
tree, and every token vector afterwards pays only queue/process/dequeue on
stationary crossbar weights. The engine then serves a REQUEST STREAM against
that installed program: ragged prompts, staggered arrivals, per-request
decode budgets, slot-based continuous batching with jit-stable shapes.

Load shapes:
  (default)            synchronized arrivals — every request at t=0, one
                       prompt length, one decode budget (the legacy regime)
  --trace poisson:RATE staggered Poisson arrivals at RATE req/s with ragged
                       prompt lengths in [prompt_len/2, prompt_len] and
                       per-request max_new in [1, gen]
  --arrivals a,b,c     explicit arrival offsets (seconds), one per request
  --static             the legacy monolithic static-batch loop (one batched
                       prefill + lockstep decode) for A/B against the engine

``--reprogram`` restores the per-call STE path (the network re-programs
every forward) for A/B measurement of the program-once speedup. ``--int8``
stores the digital weights in the paper's number format. Recurrent archs
(xlstm, rglru) serve through per-slot hidden-state insertion/reset — no
longer rejected.

``--mesh data:D,model:M`` serves through the SHARDED engine
(`runtime.engine.ShardedServeEngine`, DESIGN.md §11): decode slots shard
over the data axis, programmed crossbar bit lines over the model axis, and
the decode output is bit-equal to the single-device engine. Combined with
``--cores N`` the per-core CM_* ledgers additionally report per mesh
device (`CoreSchedule.mesh_placement`). The legacy ``DxM`` spelling keeps
the single-device engine. CPU hosts must force the device count BEFORE
launch: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

``--models NAME:EXEC[,NAME:EXEC...]`` switches to the MULTI-TENANT server
(`runtime.server.ModelServer`, DESIGN.md §12): every listed model is kept
resident in one process — the AIMC ones co-programmed against a single
shared crossbar budget (`core.program.TilePool`; cap it with
``--tile-budget``) — and an interleaved Poisson trace is routed by tenant:

    PYTHONPATH=src python -m repro.launch.serve --smoke \
        --models granite-8b:aimc,xlstm-350m:digital \
        --tenants premium:granite-8b:2,standard:granite-8b:1:sjf,\
batch:xlstm-350m --requests 16 --trace poisson:200

``--tenants NAME:MODEL[:WEIGHT[:ADMISSION]]`` declares each tenant's
routing target, fair-share weight for its model's decode slots, and
admission order (default: one fifo tenant per model, weight 1). The run
prints per-tenant tok/s, p50/p99 TTFT/TPOT, Jain's quota-fairness index
and the pool utilization, and exits nonzero if any per-tenant CM_* ledger
fails to reconcile or a tenant with requests was starved of all tokens.

``--page-size P`` swaps the dense per-slot KV cache for a paged one
(DESIGN.md §15): fixed P-row pages in one pool, addressed through a traced
page table, bit-equal to the dense engine. On top of it ``--prefix-cache``
shares content-hashed prompt-prefix pages across requests (a hit admits
without re-running the shared span's prefill — shape the trace with
``--shared-prefix K``) and ``--prefill-chunk C`` runs long prefills as
bounded legs interleaved with decode. ``--paged-verify`` makes the run exit
nonzero unless the page ledger reconciles exactly, nothing recompiled after
warmup, and the exactly-once prefill contract held. All of it passes
through to the multi-tenant server (``--models``), where
`tenancy.TenantPolicy.max_pages` additionally caps each tenant's page take.

``--drift NU`` ages the programmed conductances along the power law on the
serve clock and ``--chaos kill:CORE@CHUNK,corrupt:CORE@CHUNK[:MAG]``
injects deterministic faults on the chunk-dispatch clock (DESIGN.md §14):
the engine probes the live states at chunk boundaries against the digital
oracle, drains dead cores onto peers, hot-reprograms past
``--health-threshold``, and the run exits nonzero unless every request
retires, every fault fires, and the CM_* + recal CM_INITIALIZE books close
exactly. ``--heartbeat PATH`` beats a liveness file per chunk/pass.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=12,
                    help="decode budget: max_new per request (includes the "
                         "prefill's first token)")
    ap.add_argument("--slots", type=int, default=0,
                    help="decode slots (continuous-batching batch rows); "
                         "0 -> min(requests, 8)")
    ap.add_argument("--trace", default="",
                    help="synthetic load: poisson:RATE (req/s, staggered "
                         "ragged arrivals); default synchronized")
    ap.add_argument("--arrivals", default="",
                    help="explicit comma-separated arrival offsets in "
                         "seconds, one per request")
    ap.add_argument("--static", action="store_true",
                    help="legacy monolithic static-batch loop (A/B baseline)")
    ap.add_argument("--eos", type=int, default=-1,
                    help="EOS token id for early retirement (-1: disabled)")
    ap.add_argument("--admission", default="fifo", choices=["fifo", "sjf"])
    ap.add_argument("--decode-chunk", dest="decode_chunk", type=int,
                    default=1,
                    help="decode steps per jitted scan chunk (k): retirement"
                         " runs on-device and the host syncs once per k "
                         "steps, double-buffered (DESIGN.md §13); 1 = the "
                         "per-step loop")
    ap.add_argument("--page-size", dest="page_size", type=int, default=0,
                    help="paged slot cache (DESIGN.md §15): KV pages of "
                         "this many token rows behind a traced page table "
                         "(transformer archs; recurrent archs page state "
                         "snapshots). Decode stays bit-equal to the dense "
                         "cache. 0 = dense")
    ap.add_argument("--pages", type=int, default=0,
                    help="page-pool size including the scratch page "
                         "(0: sized so every slot can hold a max-length "
                         "request)")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true",
                    help="content-hashed prefix cache over full pages: a "
                         "request whose prompt prefix is resident admits "
                         "WITHOUT re-running the shared span's prefill "
                         "(needs --page-size)")
    ap.add_argument("--prefill-chunk", dest="prefill_chunk", type=int,
                    default=0,
                    help="run prefills as bounded legs of this many tokens, "
                         "interleaved with decode chunks (needs "
                         "--page-size; 0 = one full-width prefill)")
    ap.add_argument("--shared-prefix", dest="shared_prefix", type=int,
                    default=0,
                    help="make the first K prompt tokens identical across "
                         "every request (the shared-system-prompt shape "
                         "the prefix cache exists for)")
    ap.add_argument("--paged-verify", dest="paged_verify",
                    action="store_true",
                    help="hard acceptance for a paged run: exit nonzero "
                         "unless the page ledger reconciles exactly, no "
                         "closure recompiled after warmup, and (with "
                         "--shared-prefix + --prefix-cache, synchronized, "
                         "unchunked) the shared span was prefilled exactly "
                         "once")
    ap.add_argument("--mesh", default="1x1",
                    help="device mesh: 'data:D,model:M' serves through the "
                         "sharded engine (slots over data, crossbar bit "
                         "lines over model; bit-equal to the single-device "
                         "path); legacy 'DxM' keeps the single-device "
                         "engine. Needs D*M visible devices (CPU: set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N before launch)")
    ap.add_argument("--exec", dest="exec_mode", default="digital",
                    choices=["digital", "aimc"])
    ap.add_argument("--reprogram", action="store_true",
                    help="legacy AIMC path: re-program every forward call "
                         "(per-call STE) instead of program-once/apply-many")
    ap.add_argument("--cores", type=int, default=1,
                    help="virtual AIMC cores: the MappingPlan spreads the "
                         "programmed matrices over this many per-core tile "
                         "contexts and serving reports per-core CM_*/comm "
                         "ledgers (core.schedule)")
    ap.add_argument("--pipeline", action="store_true",
                    help="price the multi-core schedule with the "
                         "position-pipelined latency law instead of the "
                         "sequential mutex chain")
    ap.add_argument("--int8", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--drift", type=float, default=0.0,
                    help="conductance drift exponent nu (power-law decay of "
                         "the programmed weights on the serve clock); the "
                         "health monitor probes at chunk boundaries and "
                         "hot-reprograms cores whose output error passes "
                         "--health-threshold (DESIGN.md §14). 0 = off")
    ap.add_argument("--drift-t0", dest="drift_t0", type=float, default=0.05,
                    help="drift reference time t0 in seconds (decay starts "
                         "once program age exceeds t0)")
    ap.add_argument("--chaos", default="",
                    help="deterministic fault injection on the chunk-"
                         "dispatch clock: kill:CORE@CHUNK / "
                         "corrupt:CORE@CHUNK[:MAG], comma-joined (e.g. "
                         "'corrupt:0@2:0.5,kill:1@4'). The engine must "
                         "detect, drain and hot-reprogram with exact CM_* "
                         "books — the run exits nonzero if any request is "
                         "lost, any event never fires, or the recal ledger "
                         "drifts")
    ap.add_argument("--health-threshold", dest="health_threshold",
                    type=float, default=0.05,
                    help="per-core relative probe error that triggers hot "
                         "recalibration")
    ap.add_argument("--heartbeat", default="",
                    help="liveness file beaten once per chunk (engine) or "
                         "pass (server) with slot occupancy and the last-"
                         "chunk wall timestamp (fault_tolerance.Heartbeat)")
    ap.add_argument("--models", default="",
                    help="multi-tenant server: NAME:EXEC[,NAME:EXEC...] "
                         "(EXEC: aimc|digital) keeps every listed model "
                         "resident — AIMC ones co-programmed on ONE shared "
                         "TilePool — and routes a mixed trace by tenant "
                         "(supersedes --arch/--exec)")
    ap.add_argument("--tenants", default="",
                    help="with --models: NAME:MODEL[:WEIGHT[:ADMISSION]]"
                         "[,...] — routing target, fair-share slot weight "
                         "and fifo/sjf admission per tenant (default: one "
                         "fifo tenant per model, weight 1)")
    ap.add_argument("--tile-budget", type=int, default=0,
                    help="with --models: cap the shared pool at this many "
                         "crossbar tiles per context (0: uncapped); "
                         "co-programmed models exceeding it together fail "
                         "with CapacityError at program time")
    ap.add_argument("--placement", default="",
                    help="auto[:BUDGET] — search the analog/digital split "
                         "per layer with the cost-model placer "
                         "(core.placement, DESIGN.md §16) instead of the "
                         "default MappingPlan patterns. BUDGET caps the "
                         "crossbar tiles per context; a model whose "
                         "profitable layers exceed it serves through a "
                         "time-multiplexed rotation plan, reprogramming "
                         "cold groups at decode-chunk boundaries (billed "
                         "as CM_INITIALIZE per swap). Needs --exec aimc")
    ap.add_argument("--placement-verify", dest="placement_verify",
                    action="store_true",
                    help="hard acceptance for a --placement run: exit "
                         "nonzero unless every request served, tokens are "
                         "bit-equal to the all-digital static oracle, "
                         "every rotation state packs within the budget, "
                         "the per-swap CM_INITIALIZE books close exactly, "
                         "and no closure recompiled after warmup")
    ap.add_argument("--swap-every", dest="swap_every", type=int, default=1,
                    help="with an overflowing --placement auto:BUDGET: "
                         "advance the rotation one state every this many "
                         "decode chunks (default 1)")
    ap.add_argument("--tile-rows", dest="tile_rows", type=int, default=0,
                    help="crossbar word lines per physical tile "
                         "(0: AimcConfig default, 512). Smaller tiles "
                         "split matrices into more row blocks — the knob "
                         "CI uses to force capacity overflow on smoke "
                         "models")
    ap.add_argument("--adc-alpha", dest="adc_alpha", type=float, default=0.0,
                    help="ADC clipping alpha (0: AimcConfig default)")
    args = ap.parse_args(argv)
    args.placement_budget = 0
    if args.placement:
        mode, _, budget = args.placement.partition(":")
        if mode != "auto" or (budget and not budget.isdigit()):
            ap.error(f"--placement {args.placement!r}: expected "
                     "auto or auto:BUDGET (BUDGET a positive integer)")
        if budget and int(budget) < 1:
            ap.error(f"--placement budget must be >= 1, got {budget}")
        args.placement_budget = int(budget) if budget else 0
        if args.exec_mode != "aimc" or args.reprogram:
            ap.error("--placement searches the programmed AIMC path "
                     "(--exec aimc, without --reprogram)")
        for on, name in [(args.models, "--models"),
                         (args.static, "--static"),
                         (args.drift, "--drift"), (args.chaos, "--chaos"),
                         (args.prefix_cache, "--prefix-cache"),
                         (args.prefill_chunk, "--prefill-chunk")]:
            if on:
                ap.error(f"--placement cannot combine with {name} "
                         "(rotation swaps and cached/chunked prefill "
                         "spans or mid-trace repairs do not compose)")
    if args.placement_verify:
        if not args.placement:
            ap.error("--placement-verify requires --placement")
        if args.trace or args.arrivals or args.eos >= 0:
            ap.error("--placement-verify compares against the synchronized "
                     "static oracle: drop --trace/--arrivals/--eos")
    if args.swap_every < 1:
        ap.error(f"--swap-every must be >= 1, got {args.swap_every}")
    if args.tile_rows < 0 or args.adc_alpha < 0:
        ap.error("--tile-rows/--adc-alpha must be >= 0")
    if args.chaos or args.drift:
        flag = "--chaos" if args.chaos else "--drift"
        if args.exec_mode != "aimc" or args.reprogram:
            ap.error(f"{flag} degrades/repairs PROGRAMMED crossbar states: "
                     "it requires --exec aimc without --reprogram")
        if args.static or args.models:
            ap.error(f"{flag} runs through the engine's chunk-boundary "
                     "resilience tick (drop --static/--models)")
    if args.chaos and args.cores < 2:
        ap.error("--chaos needs --cores >= 2: a killed core drains onto "
                 "surviving peers, so there must be at least one")
    if args.drift < 0:
        ap.error(f"--drift must be >= 0, got {args.drift}")
    if args.models:
        for on, name in [(args.static, "--static"), (args.int8, "--int8"),
                         (args.reprogram, "--reprogram"),
                         (args.cores > 1, "--cores"),
                         (args.pipeline, "--pipeline"),
                         (args.arrivals, "--arrivals"),
                         (args.paged_verify, "--paged-verify"),
                         (args.shared_prefix, "--shared-prefix")]:
            if on:
                ap.error(f"{name} is a single-model option; --models serves "
                         "through the multi-tenant ModelServer")
    elif args.tenants or args.tile_budget:
        ap.error("--tenants/--tile-budget require --models")
    if ((args.cores > 1 or args.pipeline)
            and (args.exec_mode != "aimc" or args.reprogram)):
        ap.error("--cores/--pipeline require the programmed AIMC path "
                 "(--exec aimc, without --reprogram): the multi-core "
                 "schedule lowers an installed AimcProgram")
    if args.trace and args.arrivals:
        ap.error("--trace and --arrivals are mutually exclusive")
    if args.static and (args.trace or args.arrivals):
        ap.error("--static serves one synchronized batch; staggered "
                 "traces/arrivals need the engine")
    if args.decode_chunk < 1:
        ap.error(f"--decode-chunk must be >= 1, got {args.decode_chunk}")
    if args.static and args.decode_chunk > 1:
        ap.error("--decode-chunk applies to the engine's scanned decode "
                 "loop; --static is the legacy lockstep baseline")
    if args.page_size < 0 or args.pages < 0 or args.prefill_chunk < 0 \
            or args.shared_prefix < 0:
        ap.error("--page-size/--pages/--prefill-chunk/--shared-prefix "
                 "must be >= 0")
    if args.page_size == 0 and (args.prefix_cache or args.prefill_chunk
                                or args.pages or args.paged_verify):
        ap.error("--prefix-cache/--prefill-chunk/--pages/--paged-verify "
                 "require --page-size")
    if args.page_size and args.static:
        ap.error("--page-size serves through the slot engine; --static is "
                 "the legacy dense-batch baseline")
    if args.shared_prefix and args.shared_prefix >= args.prompt_len:
        ap.error(f"--shared-prefix {args.shared_prefix} must leave every "
                 f"request a unique continuation (< --prompt-len "
                 f"{args.prompt_len})")
    return args


def parse_mesh(arg: str):
    """(shape, axes, sharded) from a --mesh string.

    ``data:D,model:M`` (any subset/order of named axes) selects the sharded
    engine; the legacy ``DxM`` / ``PxDxM`` positional syntax keeps the
    single-device `ServeEngine` (mesh used for context only, as before)."""
    if ":" in arg:
        pairs = [p.split(":", 1) for p in arg.split(",")]
        bad = [p for p in pairs if len(p) != 2 or not p[1].isdigit()
               or int(p[1]) < 1]
        if bad or not pairs:
            raise SystemExit(f"--mesh {arg!r}: expected AXIS:SIZE[,AXIS:SIZE]"
                             " with SIZE >= 1 (e.g. data:2,model:1)")
        axes = tuple(name for name, _ in pairs)
        if len(set(axes)) != len(axes):
            raise SystemExit(f"--mesh {arg!r}: duplicate axis")
        return tuple(int(s) for _, s in pairs), axes, True
    try:
        shape = tuple(int(s) for s in arg.split("x"))
    except ValueError:
        raise SystemExit(f"--mesh {arg!r}: expected DxM / PxDxM or the "
                         "named AXIS:SIZE[,AXIS:SIZE] syntax") from None
    if len(shape) not in (2, 3) or any(s < 1 for s in shape):
        raise SystemExit(f"--mesh {arg!r}: positional syntax takes 2 (DxM) "
                         "or 3 (PxDxM) sizes, each >= 1")
    axes = {2: ("data", "model"), 3: ("pod", "data", "model")}[len(shape)]
    return shape, axes, False


def parse_named_mesh(arg: str):
    """(shape, axes) from a --mesh string, REQUIRING the named syntax.

    The benchmark/sharded entry points take only ``data:D,model:M`` — the
    legacy positional spelling means "single-device engine" in this CLI and
    must not silently select the sharded one elsewhere."""
    shape, axes, sharded = parse_mesh(arg)
    if not sharded:
        raise SystemExit(f"--mesh {arg!r}: this path takes the named "
                         "AXIS:SIZE[,AXIS:SIZE] syntax (e.g. "
                         "data:2,model:1); the positional DxM spelling "
                         "selects the single-device engine in launch.serve")
    return shape, axes


def force_host_device_count(arg: str):
    """Parse a named --mesh spec and force the XLA host-platform device
    count to fit it. MUST run before the first jax backend use (the device
    count is fixed at backend init) — call it at the top of a ``__main__``
    entry point, never from library code. Returns (shape, axes).

    The flag is a silent no-op once the backend is up, so after setting it
    this VERIFIES the device count actually covers the mesh (initializing
    the backend right here if it was not already) and exits nonzero
    otherwise — a data:2 run must never proceed on 1 device while claiming
    a 2-device mesh."""
    import math
    import os
    shape, axes = parse_named_mesh(arg)
    need = math.prod(shape)
    if need > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={need} "
            + os.environ.get("XLA_FLAGS", ""))
        import jax
        have = jax.device_count()
        if have < need:
            raise SystemExit(
                f"--mesh {arg!r} needs {need} devices but the JAX backend "
                f"is already initialized with {have}: XLA_FLAGS was set too "
                f"late to take effect. Export XLA_FLAGS=--xla_force_host_"
                f"platform_device_count={need} before the process first "
                f"touches jax, or call force_host_device_count() before "
                f"any jax use.")
    return shape, axes


def build_requests(args, vocab: int, min_prompt: int = 1):
    """The synthetic request stream the CLI serves. ``min_prompt`` floors
    the ragged prompt lengths (vlm prompts must cover the patch prefix)."""
    from repro.runtime.batcher import poisson_trace, synchronized_trace
    n, p, g = args.requests, args.prompt_len, args.gen
    if p < min_prompt:
        raise SystemExit(f"--prompt-len {p} < minimum prompt length "
                         f"{min_prompt} for this arch")
    if args.trace:
        kind, _, param = args.trace.partition(":")
        if kind != "poisson":
            raise SystemExit(f"unknown --trace kind {kind!r} "
                             "(supported: poisson:RATE)")
        rate = float(param or "100")
        return poisson_trace(n, rate, seed=args.seed,
                             prompt_len=(max(min_prompt, p // 2), p),
                             max_new=(1, g), vocab=vocab)
    base = synchronized_trace(n, prompt_len=p, max_new=g, seed=args.seed,
                              vocab=vocab)
    if args.arrivals:
        offs = [float(x) for x in args.arrivals.split(",")]
        if len(offs) != n:
            raise SystemExit(f"--arrivals needs {n} offsets, got {len(offs)}")
        base = [dataclasses.replace(r, arrival=t) for r, t in zip(base, offs)]
    return base


def apply_shared_prefix(requests, k: int):
    """Overwrite the first ``k`` tokens of every prompt with request 0's —
    the shared-system-prompt shape the prefix cache exists for. Prompts
    shorter than ``k`` become a prefix of the shared span."""
    if not k:
        return requests
    shared = requests[0].prompt[:k]
    return [dataclasses.replace(
        r, prompt=(shared + r.prompt[k:] if len(r.prompt) > k
                   else shared[:len(r.prompt)]))
        for r in requests]


def parse_models(arg: str):
    """``NAME:EXEC[,NAME:EXEC...]`` -> list of `runtime.server.ModelSpec`.
    NAME is an arch-registry id (aliases fine) and doubles as the model id
    requests route by."""
    from repro.runtime.server import ModelSpec
    specs = []
    for part in arg.split(","):
        name, _, mode = part.partition(":")
        if not name:
            raise SystemExit(f"--models {arg!r}: empty model name")
        try:
            specs.append(ModelSpec(name=name, arch=name,
                                   exec_mode=mode or "digital"))
        except ValueError as e:
            raise SystemExit(f"--models {arg!r}: {e}") from None
    return specs


def parse_tenants(arg: str, specs):
    """``NAME:MODEL[:WEIGHT[:ADMISSION]][,...]`` -> `TenantPolicy` list."""
    from repro.runtime.tenancy import TenantPolicy
    known = {s.name for s in specs}
    out = []
    for part in arg.split(","):
        fields = part.split(":")
        if len(fields) < 2 or len(fields) > 4:
            raise SystemExit(f"--tenants {arg!r}: expected "
                             "NAME:MODEL[:WEIGHT[:ADMISSION]], got {part!r}")
        name, model = fields[0], fields[1]
        if model not in known:
            raise SystemExit(f"--tenants {arg!r}: tenant {name!r} routes to "
                             f"{model!r}, not in --models ({sorted(known)})")
        try:
            out.append(TenantPolicy(
                name=name, model=model,
                weight=float(fields[2]) if len(fields) > 2 else 1.0,
                admission=fields[3] if len(fields) > 3 else "fifo"))
        except ValueError as e:
            raise SystemExit(f"--tenants {arg!r}: {e}") from None
    return out


def _run_server(args):
    """The --models path: multi-tenant multi-model serving over one pool."""
    import jax

    from repro.configs import get_arch
    from repro.launch.mesh import make_mesh
    from repro.runtime.server import build_server
    from repro.runtime.tenancy import mixed_poisson_trace

    specs = parse_models(args.models)
    tenants = parse_tenants(args.tenants, specs) if args.tenants else None
    shape, axes, sharded = parse_mesh(args.mesh)
    mesh = make_mesh(shape, axes) if sharded else None

    rate = 100.0
    if args.trace:
        kind, _, param = args.trace.partition(":")
        if kind != "poisson":
            raise SystemExit(f"unknown --trace kind {kind!r} "
                             "(supported: poisson:RATE)")
        rate = float(param or "100")

    p, g = args.prompt_len, args.gen
    n_slots = args.slots or 4
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        t0 = time.time()
        server = build_server(
            specs, tenants, smoke=args.smoke, n_slots=n_slots,
            prompt_pad=p, max_seq=p + g, seed=args.seed,
            tiles_per_context=args.tile_budget or None,
            eos_id=None if args.eos < 0 else args.eos, mesh=mesh,
            page_size=args.page_size, n_pages=args.pages,
            prefix_cache=args.prefix_cache,
            prefill_chunk=args.prefill_chunk)
        server.warmup()
        print(f"[serve] {len(specs)} model(s) resident, "
              f"{len(server.policies)} tenant(s), {n_slots} slots each; "
              f"built+warmed in {time.time() - t0:.2f}s")
        if server.pool is not None:
            print(f"[serve] {server.pool.summary()} "
                  f"(crossbar-capacity utilization "
                  f"{server.pool.utilization * 100:.0f}%)")

        def vocab(s):
            a = get_arch(s.arch)
            return (a.smoke_cfg if args.smoke else a.model_cfg).vocab

        trace = mixed_poisson_trace(
            list(server.policies.values()), args.requests, rate,
            vocab_of={s.name: vocab(s) for s in specs}, seed=args.seed,
            prompt_len=(max(1, p // 2), p), max_new=(1, g))
        heartbeat = None
        if args.heartbeat:
            from repro.runtime.fault_tolerance import Heartbeat
            heartbeat = Heartbeat(args.heartbeat)
        report = server.serve(trace, heartbeat=heartbeat)
        print(f"[serve] {report.summary()}")
        for m in server.engines:
            shares = server.fair_shares(m)
            print(f"  {m}: entitled slots "
                  + ", ".join(f"{t}={v:.2f}" for t, v in sorted(shares.items())))

        recon = server.reconcile(report)
        for m, ok in sorted(recon.items()):
            label = {True: "True", False: "FAILED", None: "n/a (digital)"}[ok]
            print(f"  {m}: per-tenant CM_* ledgers reconcile against "
                  f"program.mvm_counts(): {label}")
        stats = report.tenant_stats()
        starved = [name for name, st in stats.items()
                   if st.n_requests > 0 and st.generated_tokens == 0]
        if starved:
            print(f"[serve] STARVED tenants (had requests, got 0 tokens): "
                  f"{starved}")
        if any(ok is False for ok in recon.values()) or starved:
            raise SystemExit(1)
        return report


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache

    args = parse_args(argv)
    enable_compile_cache()
    if args.models:
        return _run_server(args)
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.core.aimc import AimcConfig
    from repro.launch.mesh import make_mesh
    from repro.models.layers import Execution
    from repro.runtime.engine import ServeEngine, ShardedServeEngine

    spec = get_arch(args.arch)
    if args.smoke:
        spec = dataclasses.replace(spec, model_cfg=spec.smoke_cfg)
    cfg = spec.model_cfg
    if spec.family == "audio":
        raise SystemExit("serve.py drives decoder-only LMs; the enc-dec "
                         "audio family decodes via launch.steps")

    shape, axes, sharded = parse_mesh(args.mesh)
    if sharded and args.static:
        raise SystemExit("--static is the single-device A/B oracle; "
                         "the sharded engine needs the named-mesh engine "
                         "path (drop --static or use the legacy DxM syntax)")
    mesh = make_mesh(shape, axes)
    aimc_kw = {}
    if args.tile_rows:
        aimc_kw["tile_rows"] = args.tile_rows
    if args.adc_alpha:
        aimc_kw["adc_alpha"] = args.adc_alpha
    aimc_cfg = AimcConfig(**aimc_kw)
    exe = (Execution(mode="aimc", aimc=aimc_cfg, compute_dtype="float32",
                     programmed=not args.reprogram)
           if args.exec_mode == "aimc"
           else Execution(compute_dtype="float32" if args.smoke
                          else "bfloat16", serve_int8=args.int8))

    model = spec.model_module()
    b, p, g = args.requests, args.prompt_len, args.gen
    max_seq = p + g
    requests = build_requests(
        args, cfg.vocab,
        min_prompt=cfg.n_patches if spec.family == "vlm" else 1)
    requests = apply_shared_prefix(requests, args.shared_prefix)

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(args.seed), cfg)
        if args.int8:
            from repro.core.quant import quantize_params_int8
            from repro.launch.shardings import (EXPERT_IN, EXPERT_OUT,
                                                IN_PROJ, OUT_PROJ)
            params = quantize_params_int8(
                params, IN_PROJ | OUT_PROJ | EXPERT_IN | EXPERT_OUT
                | {"unembed"})

        program = None
        schedule = None
        health = None
        chaos = None
        rotation = None
        rotation_params = None
        placement = None
        params_raw = params
        if args.exec_mode == "aimc" and not args.reprogram:
            # CM_INITIALIZE: program the whole network once, outside the
            # serving loop (paper §IV-B). --cores spreads the matrices over
            # per-core tile contexts (paper Fig. 2).
            from repro.core.program import MappingPlan, program_model
            from repro.core.schedule import CoreSchedule
            t0 = time.time()
            plan = MappingPlan(n_contexts=args.cores)
            if args.placement:
                # cost-model-driven auto-placement (DESIGN.md §16): search
                # the analog/digital split under the tile budget; an
                # overflowing model gets a rotation plan whose states
                # time-multiplex the freed headroom
                from repro.core.placement import plan_placement
                placement = plan_placement(
                    params, plan, aimc_cfg,
                    tiles_per_context=args.placement_budget or None,
                    n_contexts=args.cores, swap_every=args.swap_every)
                print(f"[serve] {placement.summary()}")
                plan = (placement.rotation.plan()
                        if placement.rotation is not None
                        else placement.plan)
            prog_key = jax.random.PRNGKey(args.seed + 2)
            program = program_model(params, plan, aimc_cfg, prog_key)
            if placement is not None and placement.rotation is not None:
                # one uncapped program over every sometimes-analog layer;
                # each rotation state installs only its resident subset
                # (the rest serve digitally from the raw weights)
                rotation = placement.rotation
                rotation_params = tuple(
                    program.install_subset(params_raw, ns)
                    for ns in rotation.states())
                params = rotation_params[0]
            else:
                params = program.install(params)
            jax.block_until_ready(
                [st.w_q for st in program.states])
            print(f"[serve] programmed in {time.time() - t0:.2f}s: "
                  f"{program.summary()}")
            schedule = CoreSchedule.from_program(program,
                                                 pipelined=args.pipeline)
            if args.cores > 1 or args.pipeline:
                print(f"[serve] {schedule.summary()}")
            if args.drift or args.chaos:
                # drift-aware serving (DESIGN.md §14): reference weights +
                # programming keys captured off the RAW tree so hot
                # reprogramming is bit-exact
                from repro.core import noise as noise_lib
                from repro.runtime.chaos import parse_chaos
                from repro.runtime.health import HealthPolicy, build_health
                noise = (noise_lib.drift_only(nu=args.drift,
                                              t0=args.drift_t0)
                         if args.drift else None)
                health = build_health(
                    program, params_raw, plan, prog_key,
                    policy=HealthPolicy(threshold=args.health_threshold),
                    noise=noise)
                chaos = parse_chaos(args.chaos) if args.chaos else None
                what = " + ".join(
                    ([f"drift nu={args.drift:g} t0={args.drift_t0:g}s"]
                     if args.drift else [])
                    + ([f"chaos [{', '.join(e.describe() for e in chaos.events)}]"]
                       if chaos else []))
                print(f"[serve] resilience: {what}; probe threshold "
                      f"{args.health_threshold:g}")

        print(f"[serve] {spec.arch_id} exec={args.exec_mode} "
              f"int8={args.int8} requests={b}"
              + (" (per-call reprogram)" if args.exec_mode == "aimc"
                 and args.reprogram else ""))

        if args.static:
            return _run_static(args, spec, cfg, exe, model, params, program,
                               schedule, requests, max_seq, jnp)

        # ---- continuous batching (the deployment path) --------------------
        n_slots = args.slots or min(b, 8)
        heartbeat = None
        if args.heartbeat:
            from repro.runtime.fault_tolerance import Heartbeat
            heartbeat = Heartbeat(args.heartbeat)
        common = dict(n_slots=n_slots, prompt_pad=p, max_seq=max_seq,
                      cache_dtype=jnp.float32, family=spec.family,
                      module=spec.module, program=program, schedule=schedule,
                      eos_id=None if args.eos < 0 else args.eos,
                      admission=args.admission,
                      decode_chunk=args.decode_chunk,
                      page_size=args.page_size, n_pages=args.pages,
                      prefix_cache=args.prefix_cache,
                      prefill_chunk=args.prefill_chunk,
                      health=health, chaos=chaos, heartbeat=heartbeat,
                      rotation=rotation, rotation_params=rotation_params)
        if sharded:
            engine = ShardedServeEngine(model, cfg, exe, params, mesh=mesh,
                                        **common)
        else:
            engine = ServeEngine(model, cfg, exe, params, **common)
        t0 = time.time()
        counts0 = engine.warmup()
        print(f"[serve] engine warmed up in {time.time() - t0:.2f}s "
              f"({n_slots} slots, prompt_pad={p}, max_seq={max_seq}, "
              f"decode_chunk={args.decode_chunk}"
              + (f"; sharded over {dict(zip(axes, shape))}" if sharded
                 else "")
              + f"; compiled {counts0})")
        if args.page_size and engine.pages is not None:
            print(f"[serve] paged cache: {engine.pages.n_pages} pages x "
                  f"{args.page_size} rows (+1 scratch in the count), "
                  f"prefix_cache={args.prefix_cache}, "
                  f"prefill_chunk={args.prefill_chunk or 'off'}"
                  + (f", shared_prefix={args.shared_prefix}"
                     if args.shared_prefix else ""))

        report = engine.serve(requests)
        print(f"[serve] {report.summary()}")
        if report.n_steps == 0:
            print("  prefill-only run: no decode steps executed "
                  f"({report.n_prefills} prefills, "
                  f"{report.wall_prefill_s:.2f}s) — no decode tok/s to "
                  "report")
        else:
            print(f"  decode: {report.n_steps} batch steps in "
                  f"{report.wall_decode_s:.2f}s "
                  f"({report.wall_decode_s / report.n_steps * 1e3:.1f} "
                  f"ms/step); slot-idle lanes {report.idle_vectors}, "
                  f"retries {report.retries}, "
                  f"stragglers {len(report.stragglers)}")

        if program is not None and rotation is not None:
            # rotation books: the per-vector CM_* split varies by state, so
            # the per-request ledgers are ill-defined; what must close
            # exactly instead is the per-swap CM_INITIALIZE bill
            from repro.core.placement import reconcile_swaps
            init = program.initialize_counts()
            print(f"  CM_INITIALIZE: {init.initialize} device writes for "
                  f"the initial program ({rotation.n_states} rotation "
                  f"states over {len(rotation.all_names)} analog matrices)")
            print(f"  rotation: {report.n_swaps} swaps "
                  f"(every {rotation.swap_every} chunk(s)), swap "
                  f"CM_INITIALIZE={report.swap_initialize}, "
                  f"{report.wall_swap_s * 1e3:.0f}ms swap wall")
            for ev in report.swap_events[:3]:
                print(f"    swap@chunk{ev.chunk} -> state {ev.state}: "
                      f"{len(ev.incoming)} matrices, "
                      f"CM_INITIALIZE={ev.initialize}")
            print(f"  per-swap CM_INITIALIZE books close exactly: "
                  f"{reconcile_swaps(program, report)}")
        elif program is not None:
            init = program.initialize_counts()
            per_vec = program.mvm_counts()
            n_vec = report.useful_vectors
            roi = per_vec.scaled(n_vec)
            print(f"  CM_INITIALIZE: {init.initialize} device writes, once "
                  f"per session — independent of the {report.generated_tokens}"
                  f" generated tokens")
            print(f"  CM_* in the serving ROI ({n_vec} useful token "
                  f"vectors): queue={roi.queue} process={roi.process} "
                  f"dequeue={roi.dequeue} (per vector: {per_vec.queue}/"
                  f"{per_vec.process}/{per_vec.dequeue})")
            from repro.runtime.batcher import reconcile
            led_sum, static_sum = reconcile(program, report.records,
                                            report.observed_vectors)
            print(f"  per-request ledger sum reconciles with the program's "
                  f"static accounting: {led_sum == static_sum}")
            if sharded and schedule is not None:
                from repro.runtime.batcher import reconcile_cores
                core_sum, sched_total = reconcile_cores(
                    schedule, report.records, report.observed_vectors)
                print(f"  per-core ledgers (aggregated across shards) "
                      f"reconcile with the schedule totals: "
                      f"{core_sum == sched_total}")
                for dev, cm in sorted(engine.device_ledgers(report).items()):
                    print(f"    mesh device[{engine.model_axis}={dev}]: "
                          f"queue={cm.queue} process={cm.process} "
                          f"dequeue={cm.dequeue}")
        if health is not None:
            _verify_resilience(engine, report, requests, chaos)
        if args.page_size and engine.pages is not None:
            led = report.page_ledger
            print(f"  pages: {led.get('free', 0)} free / "
                  f"{led.get('held', 0)} held of "
                  f"{led.get('total', 0)} (ledger exact: "
                  f"{report.page_ledger_exact}); "
                  f"prefix hits {report.prefix_hits} "
                  f"({report.prefix_hit_vectors} prompt vectors never "
                  f"re-prefilled), evictions {report.page_evictions}; "
                  f"prefill legs {report.prefill_chunks}, "
                  f"prompt-pad waste {report.prefill_pad_vectors} vectors")
            if args.paged_verify:
                _verify_paged(engine, report, requests, args, counts0)
        if args.placement_verify:
            _verify_placement(engine, report, requests, args, placement,
                              program, params_raw, model, cfg, exe,
                              counts0, max_seq, jnp)
        _print_schedule(args, schedule)
        for rid in sorted(report.records)[:3]:
            rec = report.records[rid]
            print(f"  req{rid}: arrival={rec.request.arrival * 1e3:.1f}ms "
                  f"prompt={len(rec.request.prompt)} "
                  f"gen={len(rec.tokens)}/{rec.request.max_new} "
                  f"({rec.finish_reason}) ttft={rec.ttft * 1e3:.1f}ms "
                  f"latency={rec.latency * 1e3:.1f}ms "
                  f"tokens={rec.tokens[:6]}...")
        return report


def _run_static(args, spec, cfg, exe, model, params, program, schedule,
                requests, max_seq, jnp):
    """The legacy monolithic path: one synchronized batch, lockstep decode."""
    from repro.runtime.engine import static_generate
    b, p, g = args.requests, args.prompt_len, args.gen
    prompts = jnp.asarray([r.prompt for r in requests], jnp.int32)
    gen_toks, (t_prefill, t_decode) = static_generate(
        model, cfg, exe, params, prompts, g, max_seq=max_seq,
        cache_dtype=jnp.float32)
    print(f"  prefill: {b}x{p} tokens in {t_prefill:.2f}s")
    if g <= 1:
        # honest prefill-only report: a 0-step decode loop has no
        # throughput; the old script printed a tok/s line from
        # max(t_decode, 1e-9) here
        print("  decode:  0 steps (prefill-only, --gen 1) — no decode "
              "tok/s to report")
    else:
        print(f"  decode:  {g - 1} steps in {t_decode:.2f}s "
              f"({b * (g - 1) / max(t_decode, 1e-9):.1f} tok/s batched, "
              f"{t_decode / (g - 1) * 1e3:.1f} ms/step)")
    if program is not None:
        init = program.initialize_counts()
        per_vec = program.mvm_counts()
        n_vec = b * (p + g - 1)
        roi = per_vec.scaled(n_vec)
        print(f"  CM_INITIALIZE: {init.initialize} device writes, once "
              f"per session — independent of the {g} generated tokens")
        print(f"  CM_* in the serving ROI ({n_vec} token vectors): "
              f"queue={roi.queue} process={roi.process} "
              f"dequeue={roi.dequeue} (per vector: {per_vec.queue}/"
              f"{per_vec.process}/{per_vec.dequeue})")
    _print_schedule(args, schedule)
    for i in range(min(b, 3)):
        print(f"  req{i}: prompt={list(requests[i].prompt[:6])}... "
              f"-> gen={[int(t) for t in gen_toks[i]]}")
    return gen_toks


def _verify_resilience(engine, report, requests, chaos):
    """Hard acceptance for a drift/chaos run — the CI chaos smoke rides on
    this: exit nonzero if any request was lost, any scheduled fault never
    fired, the per-request CM_* books fail against the (possibly remapped)
    program, or the recalibration ledger does not close exactly."""
    from repro.runtime.batcher import reconcile
    from repro.runtime.health import reconcile_recal
    for ev in report.fault_events:
        print(f"  fault injected: {ev.describe()}")
    for ev in report.recal_events:
        print(f"  hot recal [{ev.reason}] cores={list(ev.cores)}: "
              f"{len(ev.names)} matrices reprogrammed, "
              f"CM_INITIALIZE={ev.initialize}, {ev.wall_s * 1e3:.0f}ms")
    print(f"  health: {report.probes} probes, {report.n_recals} recals, "
          f"recal CM_INITIALIZE={report.recal_initialize} (charged on top "
          f"of the session's program-once bill), "
          f"{report.wall_health_s:.2f}s health wall")
    failures = []
    if len(report.records) != len(requests):
        lost = ({r.rid for r in requests}
                - {rid for rid in report.records})
        failures.append(f"LOST {len(lost)} in-flight request(s): "
                        f"{sorted(lost)}")
    if chaos is not None and not chaos.exhausted:
        left = [e.describe() for e in chaos.events if e not in chaos.fired]
        failures.append(f"chaos events never fired: {left}")
    led_sum, static_sum = reconcile(engine.program, report.records,
                                    report.observed_vectors)
    if led_sum != static_sum:
        failures.append("per-request CM_* ledgers do not reconcile against "
                        "the recovered program")
    if not reconcile_recal(engine.program, report):
        failures.append("recalibration CM_INITIALIZE books do not close")
    if failures:
        for f in failures:
            print(f"  RESILIENCE FAILURE: {f}")
        raise SystemExit(1)
    print("  resilience books close exactly: no lost requests, every "
          "fault fired, CM_* + recal ledgers reconcile")


def _verify_paged(engine, report, requests, args, counts0):
    """Hard acceptance for a paged run — the CI paged smokes ride on this:
    exit nonzero unless every request retired, the page ledger reconciles
    exactly, no closure recompiled after warmup, the vector books close,
    and (shared-prefix + prefix-cache, synchronized, unchunked) the shared
    span was prefilled exactly once across the whole trace."""
    failures = []
    if len(report.records) != len(requests):
        lost = {r.rid for r in requests} - set(report.records)
        failures.append(f"{len(lost)} request(s) never served: "
                        f"{sorted(lost)}")
    if not report.page_ledger_exact:
        failures.append(f"page ledger does not reconcile: "
                        f"{report.page_ledger}")
    held = report.page_ledger.get("held", 0)
    cached = len(engine.prefix) if engine.prefix is not None else 0
    if held != cached:
        failures.append(f"{held} pages held at finish but {cached} prefix "
                        f"entries resident — a request leaked pages")
    if report.observed_vectors != report.useful_vectors:
        failures.append(f"device-loop vector count "
                        f"{report.observed_vectors} != per-request books "
                        f"{report.useful_vectors}")
    counts = engine.compile_counts()
    if counts != counts0:
        failures.append(f"closures recompiled after warmup: {counts0} -> "
                        f"{counts}")
    if (args.shared_prefix and args.prefix_cache and not args.prefill_chunk
            and not args.trace and not engine.recurrent):
        # synchronized + unchunked: admission is synchronous, so the
        # exactly-once contract is exact, not statistical
        span = (args.shared_prefix // args.page_size) * args.page_size
        plen = args.prompt_len
        paid = sorted(r.prefill_vectors for r in report.records.values())
        want = sorted([plen] + [plen - span] * (len(requests) - 1))
        if paid != want:
            failures.append(
                f"shared span not prefilled exactly once: per-request "
                f"prefill vectors {paid}, want one producer at {plen} and "
                f"{len(requests) - 1} sharers at {plen - span}")
        if report.prefix_hits != len(requests) - 1:
            failures.append(f"prefix hits {report.prefix_hits}, want "
                            f"{len(requests) - 1}")
    if failures:
        for f in failures:
            print(f"  PAGED FAILURE: {f}")
        raise SystemExit(1)
    print("  paged books close exactly: all requests served, page ledger "
          "reconciles, no recompiles"
          + (", shared span prefilled exactly once"
             if args.shared_prefix and args.prefix_cache
             and not args.prefill_chunk and not args.trace
             and not engine.recurrent else ""))


def _verify_placement(engine, report, requests, args, placement, program,
                      params_raw, model, cfg, exe, counts0, max_seq, jnp):
    """Hard acceptance for a --placement run — the CI placement smoke
    rides on this: exit nonzero unless every request retired, every token
    is bit-equal to the ALL-DIGITAL static oracle on the raw weights (the
    equality bar of DESIGN.md §16 — analog layers must be exact, not
    approximately right), every rotation state packs within the budget,
    the per-swap CM_INITIALIZE books close (`placement.reconcile_swaps`),
    an overflowing trace actually swapped, and nothing recompiled after
    warmup (swaps reuse the per-state executables)."""
    import dataclasses as _dc

    from repro.core.placement import reconcile_swaps
    from repro.core.tile import pack_contexts
    from repro.runtime.engine import static_generate
    failures = []
    if len(report.records) != len(requests):
        lost = {r.rid for r in requests} - set(report.records)
        failures.append(f"{len(lost)} request(s) never served: "
                        f"{sorted(lost)}")
    dig_exe = _dc.replace(exe, mode="digital")
    prompts = jnp.asarray([r.prompt for r in requests], jnp.int32)
    oracle, _ = static_generate(model, cfg, dig_exe, params_raw, prompts,
                                args.gen, max_seq=max_seq,
                                cache_dtype=jnp.float32)
    bad = [r.rid for i, r in enumerate(requests)
           if r.rid in report.records
           and report.tokens(r.rid) != [int(t) for t in oracle[i]]]
    if bad:
        failures.append(f"tokens diverge from the all-digital oracle for "
                        f"request(s) {bad}")
    rot = engine.rotation
    if rot is not None:
        for i, names in enumerate(rot.states()):
            resident = set(names)
            per = pack_contexts([c.item for c in placement.costs
                                 if c.path in resident],
                                rot.n_contexts, engine.program.cfg.tile_rows,
                                engine.program.cfg.tile_cols)
            if max(per) > rot.tiles_per_context:
                failures.append(
                    f"rotation state {i} packs to {max(per)} tiles > "
                    f"budget {rot.tiles_per_context}")
        if rot.n_states > 1 and report.n_swaps == 0:
            failures.append("overflowing plan never swapped (trace too "
                            "short for the swap cadence?)")
        if not reconcile_swaps(program, report):
            failures.append("per-swap CM_INITIALIZE books do not close")
    counts = engine.compile_counts()
    if counts != counts0:
        failures.append(f"closures recompiled after warmup: {counts0} -> "
                        f"{counts}")
    if failures:
        for f in failures:
            print(f"  PLACEMENT FAILURE: {f}")
        raise SystemExit(1)
    print("  placement books close exactly: all requests served, tokens "
          "bit-equal to the all-digital oracle"
          + (f", {report.n_swaps} swaps billed + reconciled"
             if rot is not None else "")
          + ", no recompiles")


def _print_schedule(args, schedule):
    if schedule is None or not (args.cores > 1 or args.pipeline):
        return
    from repro.core.schedule import pipelined_latency, sequential_latency
    print(f"  per-core ledgers, one token vector "
          f"(queue/process/dequeue, comm bytes, load+store bytes):")
    for led in schedule.ledgers():
        print(f"    core{led.core}: {led.cm.queue}/{led.cm.process}/"
              f"{led.cm.dequeue}  comm={led.comm_bytes}B  "
              f"io={led.load_bytes + led.store_bytes}B")
    times = schedule.phase_times()
    print(f"  modeled latency/vector (Table I-A system): "
          f"sequential={sequential_latency(times) * 1e6:.1f}us  "
          f"pipelined={pipelined_latency(times) * 1e6:.1f}us  "
          f"(law in effect: "
          f"{'pipelined' if args.pipeline else 'sequential'})")


if __name__ == "__main__":
    main()
