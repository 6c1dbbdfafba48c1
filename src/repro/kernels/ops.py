"""Jit'd dispatch wrappers around the AIMC kernels.

``aimc_matmul_v2`` is the execution-path entry point used by ``core.aimc``:
in-kernel PRNG read noise (scalar seed instead of a streamed `[KB, B, Np]`
tensor), fused bias/activation epilogue, `aimc_matmul_stacked` for
gate-fused multi-MVM stacks, and `aimc_matmul_experts` for a sparse-expert
layer's grouped call over its expert stacks. Each picks its implementation
here and only here (`resolve_impl`): ``impl="auto"`` (the default) runs the
compiled Pallas kernel on a TPU backend and the pure-jnp oracle elsewhere. The
oracle stays selectable as ``"ref"`` (health probes, tests) and the
kernel in the Pallas interpreter as ``"pallas_interpret"`` (kernel tests).
A kernel that fails to lower raises; nothing falls back to another impl.
The wrappers also normalize padding so callers never worry about block
alignment.

Under a multi-device mesh (``jax.set_mesh``) the compiled kernel runs
inside a ``shard_map`` (`_map_over_mesh`): GSPMD cannot partition a
Mosaic call. Bit lines split over ``model``, input rows over the other
axes, and each shard draws the read noise of its own block.

``aimc_matmul`` keeps the v1 contract (an explicit noise operand) for the
staged/loose comparisons and differential tests; `read_noise=None` now skips
the noise operand entirely instead of streaming zeros.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import current_mesh
from repro.kernels import ref as _ref
from repro.kernels.aimc_mvm import (EPILOGUES, aimc_expert_pallas,
                                    aimc_matmul_pallas,
                                    aimc_matmul_pallas_stacked,
                                    aimc_matmul_pallas_v2)
from repro.kernels.ref import EPILOGUE_FNS  # re-export: unfused fallbacks

IMPLS = ("auto", "ref", "pallas_interpret", "pallas_tpu")


def resolve_impl(impl: str) -> str:
    """The implementation that runs: ``"auto"`` is the compiled kernel on a
    TPU backend and the jnp oracle on any other; explicit names pass."""
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")
    if impl == "auto":
        return "pallas_tpu" if jax.default_backend() == "tpu" else "ref"
    return impl


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _pick_blocks(b: int, np_: int, block_b: int, block_n: int) -> tuple[int, int]:
    """Block sizes honoring TPU lane alignment: bN is always a multiple of
    128 that divides Np (weight columns are 128-padded at programming time;
    a non-aligned Np is a contract violation, not something to shrink the
    block below the lane width for)."""
    if np_ % 128:
        raise ValueError(
            f"Np={np_} is not 128-lane aligned; pad weights at programming "
            f"time (program_linear pads Np for exactly this reason)")
    bn = min(_round_up(block_n, 128), np_)
    while np_ % bn:
        bn -= 128
    bb = min(block_b, _round_up(b, 8))
    return bb, bn


def _check_noise_source(noise_source: str, sigma: float, impl: str) -> None:
    """The hardware PRNG (`pltpu.prng_*`) only lowers on compiled TPU; the
    counter generator is the oracle-bit-identical path everywhere else."""
    if sigma > 0.0 and noise_source == "hw" and impl != "pallas_tpu":
        raise ValueError(
            'noise_source="hw" needs the compiled TPU kernel (the '
            'interpreter and the oracle have no hardware PRNG); use "counter"')


def _map_over_mesh(call, x, w_q, s_w, s_x, seed, bias):
    """Run kernel `call` on the ambient mesh, if a multi-device one is set.

    GSPMD cannot partition a Mosaic call, so the kernel runs per shard
    inside a ``shard_map``. Bit lines (the last dim of `w_q`, `s_w` and
    `bias`) split over ``model`` when every shard keeps whole 128-lane
    blocks; input rows split over the other axes when they divide evenly.
    Whatever does not split replicates. Columns and rows are independent
    and no reduction dim is split, so each shard computes a block of the
    single-device result: the kernel gets that block's global row and
    column offsets (counter noise then draws what one device would), and
    the column blocks are gathered back into whole rows. A column-sharded
    output would leave the residual stream sharded over `d_model`, and
    GSPMD would then split the norms' and the unembedding's reductions
    across devices. `call(x, w_q, s_w, s_x, seed, bias, offsets,
    b_logical, n_logical)` runs one block."""
    mesh = current_mesh()
    b, np_ = x.shape[0], w_q.shape[-1]
    if mesh is None or mesh.size == 1:
        return call(x, w_q, s_w, s_x, seed, bias, None, b, np_)
    sizes = dict(mesh.shape)
    n_col = sizes.get("model", 1)
    col = "model" if n_col > 1 and np_ % (128 * n_col) == 0 else None
    rows = tuple(a for a in mesh.axis_names if a != "model" and sizes[a] > 1)
    rows = rows if rows and b % math.prod(sizes[a] for a in rows) == 0 else ()
    row = rows if len(rows) > 1 else (rows[0] if rows else None)
    shard_axes = rows + ((col,) if col else ())
    args = {"x": x, "w_q": w_q, "s_w": s_w, "s_x": s_x, "seed": seed,
            "bias": bias}
    args = {k: v for k, v in args.items() if v is not None}
    specs = {k: P() for k in args}
    specs["x"] = P(row)
    for k in ("w_q", "s_w", "bias"):
        if k in args:
            specs[k] = P(*([None] * (args[k].ndim - 1) + [col]))
    lead = [None] * (w_q.ndim - 3)      # the gate dim of a stacked call

    def local(a):
        b_loc, np_loc = a["x"].shape[0], a["w_q"].shape[-1]
        zero = jnp.int32(0)
        offsets = jnp.stack([
            jax.lax.axis_index(rows) * b_loc if rows else zero,
            jax.lax.axis_index(col) * np_loc if col else zero,
            jax.lax.axis_index(shard_axes) if shard_axes else zero,
        ]).astype(jnp.int32)
        y = call(a["x"], a["w_q"], a["s_w"], a["s_x"], a.get("seed"),
                 a.get("bias"), offsets, b, np_)
        if col is None:
            return y
        return jax.lax.all_gather(y, col, axis=y.ndim - 1, tiled=True)

    return jax.shard_map(local, mesh=mesh, in_specs=(specs,),
                         out_specs=P(*lead, row, None),
                         check_vma=False)(args)


def aimc_matmul(x, w_q, s_w, s_x, read_noise=None, *, adc_step: float,
                impl: str = "auto", block_b: int = 128, block_n: int = 512):
    """v1-contract fused AIMC crossbar matmul (see kernels/ref.py).

    ``read_noise=None`` means noise-off and is executed through the v2
    kernel with NO noise operand (nothing streamed); an explicit tensor
    keeps the v1 path for staged comparisons and differential tests.
    """
    if read_noise is None:
        return aimc_matmul_v2(x, w_q, s_w, s_x, adc_step=adc_step, impl=impl,
                              block_b=block_b, block_n=block_n)
    impl = resolve_impl(impl)
    if impl == "ref":
        return _ref.aimc_matmul_ref(x, w_q, s_w, s_x, read_noise, adc_step=adc_step)

    b, k = x.shape
    kb, m, np_ = w_q.shape
    bb, bn = _pick_blocks(b, np_, block_b, block_n)
    b_pad = _round_up(b, bb)
    if b_pad != b:
        x = jnp.pad(x, ((0, b_pad - b), (0, 0)))
        read_noise = jnp.pad(read_noise, ((0, 0), (0, b_pad - b), (0, 0)))
    y = aimc_matmul_pallas(
        x, w_q, s_w, s_x, read_noise,
        adc_step=adc_step, block_b=bb, block_n=bn,
        interpret=(impl == "pallas_interpret"),
    )
    return y[:b]


def aimc_matmul_v2(x, w_q, s_w, s_x, seed=None, bias=None, *,
                   adc_step: float, sigma: float = 0.0,
                   activation: str = "none", impl: str = "auto",
                   block_b: int = 128, block_n: int = 512,
                   noise_source: str = "counter"):
    """Kernel-v2 fused AIMC matmul: in-kernel noise + fused epilogue.

    `seed`/`sigma` replace the v1 noise tensor (see kernels/cprng.py for the
    counter contract); `bias` is `[Np]`-broadcastable, `activation` one of
    `EPILOGUES`. Output: f32 `[B, Np]`, epilogue already applied.
    """
    if activation not in EPILOGUES:
        raise ValueError(f"unknown epilogue {activation!r}")
    impl = resolve_impl(impl)
    _check_noise_source(noise_source, sigma, impl)
    if impl == "ref":
        return _ref.aimc_matmul_ref_v2(x, w_q, s_w, s_x, seed, bias,
                                       adc_step=adc_step, sigma=sigma,
                                       activation=activation)

    def call(x, w_q, s_w, s_x, seed, bias, offsets, b_logical, n_logical):
        b = x.shape[0]
        bb, bn = _pick_blocks(b, w_q.shape[-1], block_b, block_n)
        b_pad = _round_up(b, bb)
        xp = jnp.pad(x, ((0, b_pad - b), (0, 0))) if b_pad != b else x
        y = aimc_matmul_pallas_v2(
            xp, w_q, s_w, s_x, seed, bias,
            adc_step=adc_step, sigma=sigma, activation=activation,
            block_b=bb, block_n=bn, noise_source=noise_source,
            interpret=(impl == "pallas_interpret"), b_logical=b_logical,
            offsets=offsets, n_logical=n_logical,
        )
        return y[:b]

    return _map_over_mesh(call, x, w_q, s_w, s_x, seed, bias)


def aimc_matmul_stacked(x, w_q, s_w, s_x, seed=None, bias=None, *,
                        adc_step: float, sigma: float = 0.0,
                        activations="none", impl: str = "auto",
                        block_b: int = 128, block_n: int = 512,
                        noise_source: str = "counter"):
    """Gate-fused multi-MVM: `[G, KB, M, Np]` stack, shared `[B, K]` input.

    One weight-stationary kernel launch computes all G outputs
    (`[G, B, Np]`), sharing the input block and its DAC scale; gate g draws
    noise under `cprng.stack_seed(seed, g)` so results are bit-equal to G
    per-gate `aimc_matmul_v2` calls with the derived seeds.
    """
    impl = resolve_impl(impl)
    _check_noise_source(noise_source, sigma, impl)
    if impl == "ref":
        return _ref.aimc_matmul_stacked_ref(x, w_q, s_w, s_x, seed, bias,
                                            adc_step=adc_step, sigma=sigma,
                                            activations=activations)
    if isinstance(activations, str):
        activations = (activations,) * w_q.shape[0]

    def call(x, w_q, s_w, s_x, seed, bias, offsets, b_logical, n_logical):
        b = x.shape[0]
        bb, bn = _pick_blocks(b, w_q.shape[-1], block_b, block_n)
        b_pad = _round_up(b, bb)
        xp = jnp.pad(x, ((0, b_pad - b), (0, 0))) if b_pad != b else x
        y = aimc_matmul_pallas_stacked(
            xp, w_q, s_w, s_x, seed, bias,
            adc_step=adc_step, sigma=sigma, activations=tuple(activations),
            block_b=bb, block_n=bn, noise_source=noise_source,
            interpret=(impl == "pallas_interpret"), b_logical=b_logical,
            offsets=offsets, n_logical=n_logical,
        )
        return y[:, :b]

    return _map_over_mesh(call, x, w_q, s_w, s_x, seed, bias)


def aimc_matmul_experts(x, w_qs, s_ws, s_x, layer, block_expert, n_used, *,
                        adc_step: float, block_rows: int, impl: str = "auto"):
    """Grouped expert MVM over `[L, E, KB, M, Np]` stacks read in place
    (`aimc_mvm.aimc_expert_pallas`; contract there): ``x`` [R, KB*M] holds
    rows sorted by expert in whole blocks of ``block_rows``. Returns f32
    [G, R, Np] for the G stacks in ``w_qs``. A kernel block spans all Np
    bit lines: an expert's whole [KB, M, Np] panel of codes is one fetch."""
    impl = resolve_impl(impl)
    w_qs, s_ws = tuple(w_qs), tuple(s_ws)
    if impl == "ref":
        return _ref.aimc_expert_ref(x, w_qs, s_ws, s_x, layer, block_expert,
                                    n_used, adc_step=adc_step,
                                    block_rows=block_rows)
    return aimc_expert_pallas(
        x, w_qs, s_ws, s_x, layer, block_expert, n_used, adc_step=adc_step,
        block_rows=block_rows, block_n=w_qs[0].shape[-1],
        interpret=(impl == "pallas_interpret"))
