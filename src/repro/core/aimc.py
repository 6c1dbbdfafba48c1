"""The AIMC tile model — programming, inference and noise-aware training.

This is the paper's contribution as a composable JAX module. A dense weight
matrix is *programmed* (CM_INITIALIZE) onto one or more crossbar row blocks
(`program_linear`), after which activations flow through the fused
DAC -> crossbar -> ADC pipeline (`aimc_apply` = CM_QUEUE/CM_PROCESS/CM_DEQUEUE).

Two usage modes, matching the paper and its cited training methodology:

  * inference           — program once (with programming noise + drift folded
    in), then apply many times; optional per-call read noise.
  * noise-aware training — `aimc_linear_ste`: the forward pass re-programs on
    the fly with a fresh noise draw (noise injection, [16]) and runs the full
    quantized pipeline; the backward pass is a straight-through estimator
    (gradients flow as if y = x @ W). This makes the AIMC path a drop-in,
    differentiable replacement for any linear layer in the model zoo.

Everything is a pytree / pure function: shardable under pjit, scannable under
lax.scan, and checkpoint-friendly.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import noise as noise_lib
from repro.core.quant import QMAX, adc_step_lsb, sym_scale
from repro.kernels import ops as kernel_ops


@dataclasses.dataclass(frozen=True)
class AimcConfig:
    """Static configuration of the simulated AIMC tile + execution choices.

    Hashable/frozen so it can be a jit static argument."""

    tile_rows: int = 512           # M word lines (crossbar inputs)
    tile_cols: int = 512           # N bit lines (crossbar outputs)
    adc_alpha: float = 1.0         # ADC full-scale factor (quant.adc_step_lsb)
    input_scale: float = 0.0       # 0.0 = dynamic (max-abs); >0 = fixed scale
    noise: noise_lib.NoiseModel = noise_lib.DISABLED
    # auto = compiled Pallas kernel on a TPU backend, jnp oracle elsewhere
    # (kernels/ops.resolve_impl); ref | pallas_interpret | pallas_tpu pin one
    impl: str = "auto"
    out_dtype: str = "float32"
    # kernel v2: apply bias + activation inside the kernel's last row-block
    # step (False = exact unfused fallback, same math as separate ops).
    fuse_epilogue: bool = True
    # read-noise generator: "counter" (cprng, oracle-bit-identical; the CI
    # path) or "hw" (pltpu PRNG, compiled TPU only).
    noise_source: str = "counter"

    @property
    def adc_step(self) -> float:
        return adc_step_lsb(self.tile_rows, self.adc_alpha)


# A programmed linear layer: conductance codes + effective scales.
#
# Registered as a pytree with STATIC (k, n) metadata so programmed states can
# live inside parameter trees: `lax.scan` slices a stacked [L, ...] state to
# the per-layer state, `vmap` maps over expert stacks, and `isinstance`
# dispatch in `models.layers.linear` still works on the traced container.
@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("w_q", "s_w"), meta_fields=("k", "n"))
@dataclasses.dataclass(frozen=True)
class AimcLinearState:
    w_q: jnp.ndarray   # int8 [..., KB, M, Np] (leading dims = layer/expert stack)
    s_w: jnp.ndarray   # f32  [..., KB, Np] (drift gain/compensation folded in)
    k: int             # logical in_features
    n: int             # logical out_features

    @property
    def stack_shape(self) -> tuple[int, ...]:
        """Leading stack dims (empty for a single programmed matrix)."""
        return tuple(self.w_q.shape[:-3])

    @property
    def instances(self) -> int:
        out = 1
        for d in self.stack_shape:
            out *= d
        return out

    def with_gain(self, gain) -> "AimcLinearState":
        """Conductance drift applied as DATA: scale the effective per-column
        output scale, leaving the stored codes — and the pytree structure —
        untouched. Aged states therefore install into a parameter tree with
        an identical treedef/shape, so refreshing drift mid-serve never
        triggers a recompile."""
        return AimcLinearState(w_q=self.w_q,
                               s_w=self.s_w * jnp.float32(gain),
                               k=self.k, n=self.n)


def _pad_to(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def program_linear(w: jnp.ndarray, cfg: AimcConfig, key: jax.Array | None = None) -> AimcLinearState:
    """CM_INITIALIZE: quantize + (noisily) program a [K, N] weight matrix.

    Row blocks of `tile_rows` are independent physical-tile spans; each gets a
    per-column conductance scale. Programming noise perturbs the stored codes;
    drift and its digital compensation fold into the effective output scale.
    """
    k, n = w.shape
    m = cfg.tile_rows
    kb = _pad_to(k, m) // m
    np_ = _pad_to(n, 128)  # lane alignment for the TPU kernel
    w_pad = jnp.zeros((kb * m, np_), w.dtype).at[:k, :n].set(w)
    w_blocks = w_pad.reshape(kb, m, np_).astype(jnp.float32)

    s_w = sym_scale(w_blocks, axis=1).reshape(kb, np_)              # per (block, col)
    codes = w_blocks / s_w[:, None, :]
    if cfg.noise.enabled and key is not None:
        codes = codes + noise_lib.programming_noise(key, codes, cfg.noise)
    w_q = jnp.clip(jnp.round(codes), -QMAX, QMAX).astype(jnp.int8)

    gain = cfg.noise.drift_gain() * cfg.noise.compensation_gain()
    return AimcLinearState(w_q=w_q, s_w=s_w * gain, k=k, n=n)


def program_stacked(w: jnp.ndarray, cfg: AimcConfig,
                    key: jax.Array | None = None) -> AimcLinearState:
    """CM_INITIALIZE for a stacked weight: [..., K, N] -> stacked state.

    Leading dims (layer stacks scanned by `lax.scan`, expert stacks consumed
    by `vmap`) are programmed independently — each instance is a separate
    crossbar tenant with its own programming-noise draw."""
    lead = w.shape[:-2]
    if not lead:
        return program_linear(w, cfg, key)
    flat = w.reshape((-1,) + w.shape[-2:])
    if key is None:
        st = jax.vmap(lambda wi: program_linear(wi, cfg, None))(flat)
    else:
        keys = jax.random.split(key, flat.shape[0])
        st = jax.vmap(lambda wi, ki: program_linear(wi, cfg, ki))(flat, keys)
    return AimcLinearState(
        w_q=st.w_q.reshape(lead + st.w_q.shape[1:]),
        s_w=st.s_w.reshape(lead + st.s_w.shape[1:]),
        k=st.k, n=st.n)


def _flatten_pad_input(x: jnp.ndarray, state: AimcLinearState, cfg: AimcConfig):
    """Shared CM_QUEUE front end: flatten leading dims, pad K to whole row
    blocks, compute the DAC scale. Returns (xf [B, KB*M], s_x, lead dims)."""
    *lead, k = x.shape
    if k != state.k:
        raise ValueError(f"in_features mismatch: {k} != {state.k}")
    kb, m, np_ = state.w_q.shape[-3:]
    b = 1
    for d in lead:
        b *= d
    xf = x.reshape(b, k).astype(jnp.float32)
    if k != kb * m:
        xf = jnp.pad(xf, ((0, 0), (0, kb * m - k)))
    if cfg.input_scale > 0.0:
        s_x = jnp.full((1, 1), cfg.input_scale, jnp.float32)
    else:
        s_x = sym_scale(xf).reshape(1, 1)
    return xf, s_x, lead


def _noise_args(cfg: AimcConfig, key: jax.Array | None, active_rows: int):
    """(seed, sigma) for the in-kernel PRNG — (None, 0.0) compiles noise out."""
    if cfg.noise.enabled and key is not None and cfg.noise.sigma_read > 0.0:
        return (noise_lib.derive_read_seed(key),
                noise_lib.read_sigma_lsb(active_rows, cfg.noise))
    return None, 0.0


def _pad_bias(bias: jnp.ndarray | None, n: int, np_: int):
    if bias is None:
        return None
    bias = jnp.asarray(bias).reshape(-1).astype(jnp.float32)
    if bias.shape[0] != n:
        raise ValueError(f"bias has {bias.shape[0]} features, layer has {n}")
    return jnp.pad(bias, (0, np_ - n)) if np_ != n else bias


def aimc_apply(state: AimcLinearState, x: jnp.ndarray, cfg: AimcConfig,
               key: jax.Array | None = None, *,
               bias: jnp.ndarray | None = None,
               activation: str = "none") -> jnp.ndarray:
    """CM_QUEUE + CM_PROCESS + CM_DEQUEUE on a programmed layer.

    x: [..., K] -> [..., N]. Leading dims are flattened for the kernel.
    Read noise (when enabled) is drawn *inside* the kernel from a scalar
    seed derived off `key` — no noise tensor is ever allocated. `bias` /
    `activation` form the epilogue: fused into the kernel's last row-block
    step when `cfg.fuse_epilogue`, applied as identical f32 ops after the
    kernel otherwise.
    """
    kb, m, np_ = state.w_q.shape
    xf, s_x, lead = _flatten_pad_input(x, state, cfg)
    seed, sigma = _noise_args(cfg, key, m)
    fuse = cfg.fuse_epilogue
    y = kernel_ops.aimc_matmul_v2(
        xf, state.w_q, state.s_w, s_x, seed,
        _pad_bias(bias, state.n, np_) if fuse else None,
        adc_step=cfg.adc_step, sigma=sigma,
        activation=activation if fuse else "none",
        impl=cfg.impl, noise_source=cfg.noise_source,
    )
    y = y[:, : state.n]
    if not fuse:
        if bias is not None:
            y = y + jnp.asarray(bias).reshape(1, -1).astype(jnp.float32)
        y = kernel_ops.EPILOGUE_FNS[activation](y)
    y = y.astype(jnp.dtype(cfg.out_dtype))
    return y.reshape(*lead, state.n)


def stack_states(states, axis: int = 0) -> AimcLinearState:
    """Stack same-shape programmed states into one `[G, ...]` gate stack.

    The stacked state is the storage format of the gate-fused multi-MVM —
    build it ONCE at programming/install time (it copies the conductance
    codes); stacking per call would re-stream the weights the fused kernel
    exists to keep stationary. `axis` places the gate dim inside existing
    stack dims: layer-scanned `[L, ...]` states stack at axis=1 so
    `lax.scan`'s per-layer slice exposes the `[G, ...]` gate stack."""
    sts = list(states)
    if len(sts) < 2:
        raise ValueError("a gate stack needs at least two states")
    first = sts[0]
    if not 0 <= axis <= len(first.stack_shape):
        raise ValueError(f"axis {axis} outside stack dims "
                         f"{first.stack_shape}")
    for st in sts[1:]:
        if (st.k, st.n) != (first.k, first.n) or st.w_q.shape != first.w_q.shape:
            raise ValueError(
                f"gate stack shape mismatch: {st.w_q.shape} ({st.k},{st.n}) "
                f"vs {first.w_q.shape} ({first.k},{first.n})")
    return AimcLinearState(
        w_q=jnp.stack([st.w_q for st in sts], axis=axis),
        s_w=jnp.stack([st.s_w for st in sts], axis=axis),
        k=first.k, n=first.n)


def aimc_apply_stacked(stack: AimcLinearState, x: jnp.ndarray, cfg: AimcConfig,
                       key: jax.Array | None = None, *,
                       biases: jnp.ndarray | None = None,
                       activations="none") -> jnp.ndarray:
    """Gate-fused multi-MVM on a `[G, ...]`-stacked programmed state.

    x: [..., K] -> [G, ..., N]: ONE weight-stationary kernel launch computes
    every gate, sharing the input block and its DAC quantization.
    `activations` is one epilogue name or a per-gate tuple; `biases` is
    `[G, N]`. Gate g draws noise under `cprng.stack_seed`, so (noise off)
    the outputs are bit-equal to per-gate `aimc_apply` calls.
    """
    if len(stack.stack_shape) != 1:
        raise ValueError(
            f"aimc_apply_stacked needs one leading gate dim, got stack shape "
            f"{stack.stack_shape}")
    g_ = stack.stack_shape[0]
    kb, m, np_ = stack.w_q.shape[-3:]
    xf, s_x, lead = _flatten_pad_input(x, stack, cfg)
    seed, sigma = _noise_args(cfg, key, m)
    if isinstance(activations, str):
        activations = (activations,) * g_
    activations = tuple(activations)
    fuse = cfg.fuse_epilogue
    if biases is not None:
        biases = jnp.asarray(biases).reshape(g_, -1).astype(jnp.float32)
        if biases.shape[1] != stack.n:
            raise ValueError(f"biases have {biases.shape[1]} features, "
                             f"layer has {stack.n}")
    bias_arg = None
    if fuse and biases is not None:
        bias_arg = (jnp.pad(biases, ((0, 0), (0, np_ - stack.n)))
                    if np_ != stack.n else biases)
    y = kernel_ops.aimc_matmul_stacked(
        xf, stack.w_q, stack.s_w, s_x, seed, bias_arg,
        adc_step=cfg.adc_step, sigma=sigma,
        activations=activations if fuse else "none",
        impl=cfg.impl, noise_source=cfg.noise_source,
    )
    y = y[:, :, : stack.n]                                    # [G, B, N]
    if not fuse:
        if biases is not None:
            y = y + biases[:, None, :]
        y = jnp.stack([kernel_ops.EPILOGUE_FNS[a](y[g])
                       for g, a in enumerate(activations)])
    y = y.astype(jnp.dtype(cfg.out_dtype))
    return y.reshape(g_, *lead, stack.n)


def aimc_apply_experts(states, x: jnp.ndarray, layer, block_expert, n_used,
                       cfg: AimcConfig, *, block_rows: int) -> jnp.ndarray:
    """CM_QUEUE + CM_PROCESS + CM_DEQUEUE for a sparse-expert layer.

    ``states``: G programmed expert stacks ``[L, E, KB, M, Np]`` of one
    shape, read in place at ``layer``. ``x`` [R, K]: rows sorted by expert,
    each expert's rows in whole blocks of ``block_rows`` (block i belongs
    to expert ``block_expert[i]``; the first ``n_used`` blocks hold rows,
    padding rows are zero). Each expert quantises its rows under one DAC
    scale, the max-abs over them, as a call of `aimc_apply` over just those
    rows would. Returns [G, R, N]. Serving applies programmed states without
    a key, so no read noise is drawn here."""
    first = states[0]
    kb, m, _ = first.w_q.shape[-3:]
    e = first.w_q.shape[-4]
    r, k = x.shape
    if k != first.k:
        raise ValueError(f"in_features mismatch: {k} != {first.k}")
    xf = x.astype(jnp.float32)
    if k != kb * m:
        xf = jnp.pad(xf, ((0, 0), (0, kb * m - k)))
    if cfg.input_scale > 0.0:
        s_x = jnp.full((e,), cfg.input_scale, jnp.float32)
    else:
        row_expert = jnp.repeat(jnp.asarray(block_expert, jnp.int32),
                                block_rows)
        amax = jnp.zeros((e,), jnp.float32).at[row_expert].max(
            jnp.max(jnp.abs(xf), axis=1))
        s_x = jnp.maximum(amax, 1e-12) * (1.0 / QMAX)
    y = kernel_ops.aimc_matmul_experts(
        xf, [st.w_q for st in states], [st.s_w for st in states], s_x,
        layer, block_expert, n_used, adc_step=cfg.adc_step,
        block_rows=block_rows, impl=cfg.impl)
    return y[:, :, :first.n].astype(jnp.dtype(cfg.out_dtype))


# ---------------------------------------------------------------------------
# Noise-aware training: straight-through estimator.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def aimc_linear_ste(x: jnp.ndarray, w: jnp.ndarray, key: jax.Array, cfg: AimcConfig):
    """Differentiable AIMC linear: y = AIMC(x, W) fwd, y = x @ W bwd.

    The forward pass programs W on the fly with a fresh programming-noise draw
    and applies per-call read noise — i.e. noise-injection training [16] — so
    the learned weights become robust to the analog non-idealities.
    """
    return _aimc_fwd_value(x, w, key, cfg)


def _aimc_fwd_value(x, w, key, cfg):
    kp, kr = (jax.random.split(key) if key is not None else (None, None))
    state = program_linear(w, cfg, kp)
    return aimc_apply(state, x, cfg, kr)


def _aimc_fwd(x, w, key, cfg):
    return _aimc_fwd_value(x, w, key, cfg), (x, w)


def _aimc_bwd(cfg, res, g):
    x, w = res
    gf = g.astype(jnp.float32)
    dx = gf @ w.T.astype(jnp.float32)
    xl = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    gl = gf.reshape(-1, g.shape[-1])
    dw = xl.T @ gl
    return dx.astype(x.dtype).reshape(x.shape), dw.astype(w.dtype), None


aimc_linear_ste.defvjp(_aimc_fwd, _aimc_bwd)


def aimc_linear(x, w, cfg: AimcConfig, key: jax.Array | None = None,
                state: AimcLinearState | None = None):
    """Low-level front door (models route through `models.layers.linear`,
    which also accepts an `AimcLinearState` directly in place of `w` — the
    program-once/apply-many path built by `core.program.program_model`).

    * training / on-the-fly:     aimc_linear(x, w, cfg, key)        [STE]
    * pre-programmed inference:  aimc_linear(x, None, cfg, key, state)
    * cfg is None or technique off -> caller should use a plain matmul.
    """
    if state is not None:
        return aimc_apply(state, x, cfg, key)
    if isinstance(w, AimcLinearState):
        return aimc_apply(w, x, cfg, key)
    return aimc_linear_ste(x, w, key, cfg)
