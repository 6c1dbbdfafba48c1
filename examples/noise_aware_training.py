"""Noise-aware training (paper §III-C, citing Joshi et al. [16]).

    PYTHONPATH=src python examples/noise_aware_training.py

PCM crossbars perturb the programmed weights; the countermeasure the paper
points to is training WITH noise injection so the learned weights are robust
at deployment. This example trains the paper's 2-layer MLP on a synthetic
classification task three ways and evaluates all three on a NOISY crossbar:

  A. digital training, digital eval             (reference ceiling)
  B. digital training, noisy AIMC eval          (naive deployment)
  C. noise-aware training (AIMC STE), noisy eval (the paper's fix)

C recovers most of the gap between B and A.
"""

import jax
import jax.numpy as jnp

from repro.core.aimc import AimcConfig, aimc_linear_ste, program_linear, \
    aimc_apply
from repro.core.noise import NoiseModel

KEY = jax.random.PRNGKey(0)
N_IN, N_H, N_CLS = 256, 256, 10
_NOISE = NoiseModel(sigma_prog_min=0.08, sigma_prog_max=0.20,
                    sigma_read=0.03, drift_t_ratio=1e3)
# training injects the programming-type noise at the deployment level but a
# gentler read noise — the recipe in Joshi et al. [16]
TRAIN_CFG = AimcConfig(tile_rows=256,
                       noise=NoiseModel(sigma_prog_min=0.08,
                                        sigma_prog_max=0.20,
                                        sigma_read=0.01))
EVAL_CFG = AimcConfig(tile_rows=256, noise=_NOISE)


W_TRUE = jax.random.normal(jax.random.fold_in(KEY, 99), (N_IN, N_CLS))


def make_data(key, n=4096):
    kx, kn = jax.random.split(key)
    x = jax.random.normal(kx, (n, N_IN))
    y = jnp.argmax(x @ W_TRUE + 0.1 * jax.random.normal(kn, (n, N_CLS)), -1)
    return x, y


def init_params(key):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (N_IN, N_H)) * (2 / N_IN) ** 0.5,
            "w2": jax.random.normal(k2, (N_H, N_CLS)) * (2 / N_H) ** 0.5}


def forward_digital(p, x):
    return jax.nn.relu(x @ p["w1"]) @ p["w2"]


def forward_aimc_ste(p, x, key):
    k1, k2 = jax.random.split(key)
    h = jax.nn.relu(aimc_linear_ste(x, p["w1"], k1, TRAIN_CFG))
    return aimc_linear_ste(h, p["w2"], k2, TRAIN_CFG)


def forward_aimc_eval(p, x, key):
    """Deployment: program once with noise+drift, then run."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s1 = program_linear(p["w1"], EVAL_CFG, k1)
    s2 = program_linear(p["w2"], EVAL_CFG, k2)
    h = jax.nn.relu(aimc_apply(s1, x, EVAL_CFG, k3))
    return aimc_apply(s2, h, EVAL_CFG, k4)


def xent(logits, y):
    return jnp.mean(jax.nn.logsumexp(logits, -1)
                    - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


def train(fwd, steps=300, lr=0.05, noisy=False):
    params = init_params(jax.random.fold_in(KEY, 1))
    x, y = make_data(jax.random.fold_in(KEY, 2))

    @jax.jit
    def step(p, i):
        k = jax.random.fold_in(KEY, i)
        def loss(pp):
            logits = fwd(pp, x, k) if noisy else fwd(pp, x)
            return xent(logits, y)
        g = jax.grad(loss)(p)
        return jax.tree.map(lambda a, b: a - lr * b, p, g)

    for i in range(steps):
        params = step(params, i)
    return params


def accuracy(logits, y):
    return float(jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32)))


def noisy_accuracy(p, x, y, seeds=8):
    """Mean accuracy over several programming-noise draws (each draw is a
    fresh chip programming; single draws have multi-point variance)."""
    accs = [accuracy(forward_aimc_eval(p, x, jax.random.fold_in(KEY, 100 + i)),
                     y) for i in range(seeds)]
    return sum(accs) / len(accs)


x_te, y_te = make_data(jax.random.fold_in(KEY, 3), n=2048)

p_dig = train(forward_digital)
acc_a = accuracy(forward_digital(p_dig, x_te), y_te)
acc_b = noisy_accuracy(p_dig, x_te, y_te)

p_naw = train(forward_aimc_ste, steps=600, noisy=True)
acc_c = noisy_accuracy(p_naw, x_te, y_te)

print(f"A. digital train  -> digital eval:        {acc_a:.1%}")
print(f"B. digital train  -> noisy crossbar eval: {acc_b:.1%}")
print(f"C. noise-aware    -> noisy crossbar eval: {acc_c:.1%}")
gap = acc_a - acc_b
rec = acc_c - acc_b
print(f"noise-aware training recovers {rec / gap:.0%} of the deployment gap"
      if gap > 1e-4 else "no deployment gap at this noise level")
assert acc_c >= acc_b - 0.01, "noise-aware training should not hurt"
