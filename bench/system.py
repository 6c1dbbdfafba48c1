"""The system under test, built from a configuration file the way
``launch/serve.py --exec aimc`` builds it: ``model.init`` from the seed,
``program_model`` -> ``install``, ``ServeEngine(...)``, ``warmup()``.

The file's ``execution`` block gives the `Execution` and its `AimcConfig`,
its ``engine`` block the engine's settings. Where the configuration has q/k/v
biases, they are drawn from the seed (``init_params``; `model.init` leaves
them at zero, which would hide the bias add from the check).

Initialisation and programming run as ONE jitted call from the seed, so the
float32 weights of the mapped projections exist only inside that call and
only the int8 codes (and the digital embedding, head and norms) stay on the
device. Warm-up compiles the engine's closures at this cell's shapes only.
"""

from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

from bench.seeds import seed_key

BENCH = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def load_traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def model_fields(conf: dict) -> dict:
    """The repo's `TransformerConfig` fields set by the configuration file
    (its published keys, renamed by the file's own ``fields`` map)."""
    return {dst: conf[src] for src, dst in conf["fields"].items()}


def transformer_config(conf: dict, smoke: bool = False):
    """`TransformerConfig` of the configuration. ``smoke`` shrinks it for
    rehearsals and tests on the CPU: 2 layers of width 512 (one whole
    crossbar tile of rows, so the ADC sees the range it is sized for), 4
    query heads of 128, the file's d_ff ratio and biases, vocab 2048."""
    from repro.configs import get_arch
    spec = get_arch(conf["arch"])
    cfg = dataclasses.replace(spec.model_cfg, **model_fields(conf))
    if smoke:
        ff = cfg.d_ff * 512 // cfg.d_model
        cfg = dataclasses.replace(
            cfg, n_layers=2, d_model=512, n_heads=4, head_dim=128,
            n_kv_heads=max(1, 4 * cfg.n_kv_heads // cfg.n_heads),
            d_ff=ff - ff % 16, vocab=2048)
    return spec, cfg


def execution(conf: dict):
    """The `Execution` (and its `AimcConfig`) the configuration states."""
    from repro.core.aimc import AimcConfig
    from repro.models.layers import Execution
    ex = conf["execution"]
    return Execution(mode=ex["mode"], compute_dtype=ex["compute_dtype"],
                     programmed=ex["programmed"],
                     aimc=AimcConfig(**ex["aimc"]))


def init_params(model, cfg, key, conf: dict):
    """`model.init` from ``key``, with the q/k/v biases (where the
    configuration has them) drawn from the seed as the reference draws them
    (`decoder_aimc.qkv_biases`)."""
    from bench.references.decoder_aimc import qkv_biases
    params = model.init(key, cfg)
    blocks = params["blocks"]
    if "bq" in blocks:
        names = ("bq", "bk", "bv")
        draws = qkv_biases(key, cfg.n_layers,
                           [blocks[n].shape[-1] for n in names],
                           conf["bias_std"])
        params["blocks"] = blocks | dict(zip(names, draws))
    return params


def _path(path) -> str:
    """A parameter's tree path as `AimcProgram` names it ("blocks/wq")."""
    return "/".join(str(k.key) for k in path)


@dataclasses.dataclass
class System:
    engine: object
    cfg: object            # TransformerConfig as run
    spec: object           # ArchSpec
    program: object        # AimcProgram
    phases: dict           # set-up seconds by phase


def build(conf: dict, seed: int, smoke: bool = False,
          engine_over: dict | None = None) -> System:
    """Weights from ``seed``, programmed and installed, in a warmed-up
    `ServeEngine`. ``engine_over`` replaces engine settings (rehearsal)."""
    import jax
    import jax.numpy as jnp

    from repro.core.program import MappingPlan, program_model
    from repro.runtime.engine import ServeEngine

    spec, cfg = transformer_config(conf, smoke)
    model = spec.model_module()
    exe = execution(conf)
    plan = MappingPlan()
    phases = {}

    def make(key):
        params = init_params(model, cfg, key, conf)
        prog = program_model(params, plan, exe.aimc,
                             jax.random.fold_in(key, 2))
        # the mapped float32 leaves leave the call as empty stubs:
        # `install` puts the programmed states in their place
        mapped = set(prog.names)
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        rest = [jnp.zeros((0,), leaf.dtype) if _path(p) in mapped else leaf
                for p, leaf in flat]
        return prog, jax.tree_util.tree_unflatten(treedef, rest)

    t0 = time.perf_counter()
    prog, rest = jax.jit(make)(seed_key(seed))
    jax.block_until_ready((prog, rest))
    phases["weights_s"] = time.perf_counter() - t0
    params = prog.install(rest)
    del rest

    eng_conf = dict(conf["engine"], **(engine_over or {}))
    engine = ServeEngine(
        model, cfg, exe, params,
        n_slots=eng_conf["n_slots"], prompt_pad=eng_conf["prompt_pad"],
        max_seq=eng_conf["max_seq"],
        cache_dtype=jnp.dtype(eng_conf["cache_dtype"]),
        family=spec.family, module=spec.module, program=prog,
        decode_chunk=eng_conf["decode_chunk"])
    t0 = time.perf_counter()
    counts = engine.warmup()
    phases["warmup_s"] = time.perf_counter() - t0
    phases["compiled"] = counts
    return System(engine=engine, cfg=cfg, spec=spec, program=prog,
                  phases=phases)
