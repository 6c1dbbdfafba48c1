"""Quickstart: the ALPINE programming model in five minutes.

    PYTHONPATH=src python examples/quickstart.py

Mirrors the paper's Fig. 4 C++ sample in JAX: map a weight matrix onto
crossbars (CM_INITIALIZE), queue an input vector (CM_QUEUE), run the analog
MVM (CM_PROCESS), dequeue the result (CM_DEQUEUE) — then the fused `linear`
path every real model uses, PCM noise, and the tile-packing view.
"""

import jax
import jax.numpy as jnp

from repro.core.aimc import AimcConfig
from repro.core.aimclib import AimcContext
from repro.core.noise import NoiseModel

M, N = 1024, 1024
key = jax.random.PRNGKey(0)

# -- a fully-connected layer and one inference input -------------------------
w = jax.random.normal(key, (M, N)) * 0.02
x = jax.random.normal(jax.random.fold_in(key, 1), (4, M))

# -- 1. program the crossbars (CM_INITIALIZE) --------------------------------
cfg = AimcConfig(tile_rows=512, tile_cols=512,
                 noise=NoiseModel(sigma_read=0.003))
ctx = AimcContext(cfg, key)
ctx.map_matrix("fc1", w)
print(f"programmed 'fc1' [{M}x{N}] onto {ctx.tile_map().n_tiles} tiles "
      f"(512x512), utilization {ctx.tile_map().utilization:.0%}")

# -- 2. the instruction-level flow (paper Fig. 4) -----------------------------
ctx.queue_vector("fc1", x)          # CM_QUEUE: DAC-quantize into input memory
ctx.process("fc1")                  # CM_PROCESS: analog MVM, 100 ns
y = ctx.dequeue_vector("fc1")       # CM_DEQUEUE: ADC codes -> digital
print(f"y = AIMC(x @ W): {y.shape}, CM_* issued so far: "
      f"{ctx.instruction_counts()}")

# -- 3. the fused path + fidelity ---------------------------------------------
y_fused = ctx.linear("fc1", x)
y_exact = x @ w
rel = float(jnp.linalg.norm(y_fused - y_exact) / jnp.linalg.norm(y_exact))
print(f"relative error vs fp32 matmul: {rel:.3%}  "
      f"(8-bit DAC/ADC + PCM noise)")

# -- 4. the LSTM gate trick (paper §VIII-D) -----------------------------------
gates = [jax.random.normal(jax.random.fold_in(key, i), (306, 256)) * 0.05
         for i in range(4)]
ctx2 = AimcContext(AimcConfig(tile_rows=612, tile_cols=1074))
ctx2.map_gates("cell", gates)
h_x = jax.random.normal(jax.random.fold_in(key, 9), (1, 306))
all_gates = ctx2.linear("cell", h_x)       # ONE process -> all four gates
print(f"four LSTM gates in one CM_PROCESS: {all_gates.shape} "
      f"on {ctx2.tile_map().n_tiles} tile(s)")

# -- 5. every model in the zoo runs this as an execution mode ----------------
from repro.configs import get_arch
from repro.models.layers import Execution

spec = get_arch("llama3.2-3b")
model = spec.model_module()
params = model.init(key, spec.smoke_cfg)
toks = jnp.ones((2, 16), jnp.int32)
exe = Execution(mode="aimc", aimc=AimcConfig(),
                compute_dtype="float32")
logits, _ = model.forward(params, toks, spec.smoke_cfg, exe,
                          jax.random.PRNGKey(2))
print(f"llama3.2-3b (smoke cfg) forward through simulated crossbars: "
      f"logits {logits.shape}, finite={bool(jnp.all(jnp.isfinite(logits)))}")

# -- 6. program once, apply many (the deployment model) -----------------------
# The forward above re-programs every weight on every call (the noise-aware
# TRAINING path). Serving programs the whole network ONCE — program_model
# walks the param tree, maps every stationary projection per the MappingPlan,
# and install() substitutes the programmed states so the same model code runs
# apply-only (CM_INITIALIZE leaves the hot path entirely).
from repro.core.program import MappingPlan, program_model

serve_cfg = AimcConfig()
program = program_model(params, MappingPlan(), serve_cfg,
                        jax.random.PRNGKey(3))
print(program.summary())
served = program.install(params)
exe_srv = Execution(mode="aimc", aimc=serve_cfg, compute_dtype="float32",
                    programmed=True)
logits2, _ = model.forward(served, toks, spec.smoke_cfg, exe_srv)
print(f"programmed forward (no re-programming): logits {logits2.shape}; "
      f"CM_INITIALIZE stays {program.initialize_counts().initialize} "
      f"no matter how many tokens follow")
