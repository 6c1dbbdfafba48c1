"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --steps 1000 --mesh 16x16 --ckpt-dir /ckpts/run0 [--exec aimc]

On this CPU container use ``--smoke --mesh 1x1`` (reduced config); on a pod
the same command line runs the full config. The loop wires together every
substrate layer: deterministic sharded data, FSDP+TP step function (with
gradient accumulation + remat), atomic async checkpointing with auto-resume,
straggler detection, heartbeat, and the AIMC execution mode (noise-aware
training) when ``--exec aimc``.

XLA flags for real TPU runs (latency-hiding collectives) are appended to
XLA_FLAGS unless --no-xla-tuning.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

TPU_XLA_FLAGS = (
    " --xla_tpu_enable_async_collective_fusion=true"
    " --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true"
    " --xla_tpu_overlap_compute_collective_tc=true"
    " --xla_enable_async_all_gather=true"
)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mesh", default="1x1",
                    help="DxM or PxDxM, e.g. 16x16 or 2x16x16")
    ap.add_argument("--global-batch", type=int, default=0,
                    help="0 = the train_4k cell's batch (or 4 with --smoke)")
    ap.add_argument("--seq-len", type=int, default=0)
    ap.add_argument("--exec", dest="exec_mode", default="digital",
                    choices=["digital", "aimc"])
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced per-arch config (CPU-runnable)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-xla-tuning", action="store_true")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if not args.no_xla_tuning and not args.smoke:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + TPU_XLA_FLAGS)

    import jax
    import jax.numpy as jnp

    from repro.checkpoint import checkpoint
    from repro.configs import ShapeCell, get_arch
    from repro.core.aimc import AimcConfig
    from repro.data.pipeline import DataConfig, host_batch, make_global_array
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import dp_axes, make_mesh
    from repro.launch.shardings import to_named
    from repro.launch.steps import make_step
    from repro.models.layers import Execution
    from repro.optim import make_optimizer
    from repro.optim.schedule import warmup_cosine
    from repro.runtime.fault_tolerance import (Heartbeat, StragglerMonitor,
                                               resilient_step)

    enable_compile_cache()
    spec = get_arch(args.arch)
    if args.smoke:
        spec = dataclasses.replace(spec, model_cfg=spec.smoke_cfg)
    cfg = spec.model_cfg

    shape = tuple(int(s) for s in args.mesh.split("x"))
    axes = {2: ("data", "model"), 3: ("pod", "data", "model")}[len(shape)]
    mesh = make_mesh(shape, axes)

    gb = args.global_batch or (4 if args.smoke else 256)
    sl = args.seq_len or (32 if args.smoke else 4096)
    cell = ShapeCell("train_cli", seq_len=sl, global_batch=gb, kind="train")
    exe = (Execution(mode="aimc", aimc=AimcConfig(),
                     compute_dtype="float32" if args.smoke else "bfloat16")
           if args.exec_mode == "aimc"
           else Execution(compute_dtype="float32" if args.smoke
                          else "bfloat16"))

    with jax.set_mesh(mesh):
        bundle = make_step(spec, cell, mesh, exe)
        step_fn = jax.jit(bundle.fn,
                          in_shardings=to_named(bundle.in_shardings, mesh),
                          out_shardings=to_named(bundle.out_shardings, mesh),
                          donate_argnums=bundle.donate_argnums)

        model = spec.model_module()
        pdtype = jnp.dtype(spec.param_dtype)
        params = jax.tree.map(
            lambda x: x.astype(pdtype),
            model.init(jax.random.PRNGKey(args.seed), cfg))
        opt_state = make_optimizer(spec.optimizer)[0](params)

        start = 0
        if args.ckpt_dir:
            state_tpl = {"params": params, "opt": opt_state}
            got, tree, extra = checkpoint.restore_latest(args.ckpt_dir,
                                                         state_tpl)
            if got is not None:
                params, opt_state = tree["params"], tree["opt"]
                start = got
                print(f"[train] resumed from step {got}")

        dcfg = DataConfig(vocab=cfg.vocab, seq_len=sl, global_batch=gb,
                          seed=args.seed)
        dp = dp_axes(mesh)
        from jax.sharding import PartitionSpec as P
        bspec = P(dp, None)
        monitor = StragglerMonitor()
        hb = Heartbeat(os.path.join(args.ckpt_dir or ".", "heartbeat.json"))
        safe_step = resilient_step(step_fn)

        print(f"[train] {spec.arch_id} {args.mesh} gb={gb} seq={sl} "
              f"exec={args.exec_mode} steps {start}..{args.steps}")
        t_last = time.time()
        for step in range(start, args.steps):
            hbatch = host_batch(dcfg, step, 0, 1)
            batch = {k: jnp.asarray(v) for k, v in hbatch.items()}
            if spec.family == "vlm":
                batch["patch_embeds"] = jnp.zeros(
                    (gb, cfg.n_patches, cfg.d_model), jnp.bfloat16)
                batch["labels"] = batch["labels"].at[:, :cfg.n_patches].set(-1)
            if spec.family == "audio":
                batch = {"frames": jnp.zeros((gb, sl, cfg.d_model),
                                             jnp.bfloat16),
                         "tokens": batch["tokens"][:, :max(sl // 8, 64)],
                         "labels": batch["labels"][:, :max(sl // 8, 64)]}
            if mesh.size > 1:
                batch = make_global_array(batch, mesh, bspec)
            rng = jnp.asarray([args.seed, step], jnp.uint32)
            lr = float(warmup_cosine(jnp.asarray(step), total=args.steps))
            params, opt_state, metrics = safe_step(params, opt_state, batch,
                                                   rng)
            if (step + 1) % args.log_every == 0 or step == start:
                loss = float(metrics["loss"])
                dt = (time.time() - t_last) / args.log_every
                t_last = time.time()
                monitor.record(step, dt)
                hb.beat(step, loss=loss)
                tok_s = gb * sl / max(dt, 1e-9)
                print(f"  step {step + 1:6d} loss {loss:8.4f} "
                      f"{dt * 1e3:8.1f} ms/step {tok_s:,.0f} tok/s lr×{lr:.3f}")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                checkpoint.save_async(args.ckpt_dir, step + 1,
                                      {"params": params, "opt": opt_state},
                                      extra={"loss": float(metrics['loss'])})
        if args.ckpt_dir:
            checkpoint.save(args.ckpt_dir, args.steps,
                            {"params": params, "opt": opt_state})
        print("[train] done")
        return params


if __name__ == "__main__":
    main()
