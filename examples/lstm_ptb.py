"""The paper's LSTM exploration, end to end (paper §VIII).

    PYTHONPATH=src python examples/lstm_ptb.py

Trains the paper's character-level LSTM (one cell layer + dense softmax
head) on a synthetic Penn-Treebank-like character stream, then runs
inference in digital and AIMC modes — gates tiled side by side so ONE
CM_PROCESS computes all four gate MVMs (§VIII-D) — and reports the
analytical run-time/energy on the paper's two system configurations for
every n_h in the paper's Table II.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aimc import AimcConfig
from repro.core.costmodel import HIGH_POWER, LOW_POWER, evaluate, speedup
from repro.core.workloads import lstm_workloads
from repro.models import paper_nets

KEY = jax.random.PRNGKey(0)
VOCAB = 50                                  # printable chars, as in PTB char
NH = 256                                    # train the smallest variant here


def synthetic_ptb(key, n_seq=64, seq_len=40):
    """Markov-ish character stream: enough structure to learn."""
    trans = jax.nn.softmax(4.0 * jax.random.normal(key, (VOCAB, VOCAB)), -1)
    seqs = [jnp.zeros((n_seq,), jnp.int32)]
    k = key
    for _ in range(seq_len):
        k = jax.random.fold_in(k, 0)
        probs = trans[seqs[-1]]
        seqs.append(jax.random.categorical(k, jnp.log(probs + 1e-9), axis=-1))
    return jnp.stack(seqs, 1)               # [n_seq, seq_len+1]


def one_hot_seq(toks):
    oh = jax.nn.one_hot(toks, VOCAB)
    return jnp.moveaxis(oh, 1, 0)           # [T, B, vocab]


print(f"training the paper's LSTM (n_h={NH}) on synthetic PTB chars...")
data = synthetic_ptb(KEY)
xs = one_hot_seq(data[:, :-1])              # [T, B, 50]
ys = jnp.moveaxis(data[:, 1:], 1, 0)        # [T, B]
params = paper_nets.lstm_init(jax.random.fold_in(KEY, 1), NH, VOCAB, VOCAB)


@jax.jit
def step(p, lr=0.5):
    def loss(pp):
        out = paper_nets.lstm_forward_digital(pp, xs, NH)  # [T,B,V] softmax
        gold = jnp.take_along_axis(out, ys[..., None], -1)[..., 0]
        return -jnp.mean(jnp.log(gold + 1e-9))
    l, g = jax.value_and_grad(loss)(p)
    return jax.tree.map(lambda a, b: a - lr * b, p, g), l


for i in range(60):
    params, l = step(params)
    if i % 20 == 0:
        print(f"  step {i:3d}  char NLL {float(l):.3f}")
print(f"  final    char NLL {float(l):.3f}")

# ---- inference: digital vs AIMC (gates side by side, §VIII-D) ---------------
cfg = AimcConfig(tile_rows=NH + VOCAB + 50, tile_cols=4 * NH + 64)
y_dig = paper_nets.lstm_forward_digital(params, xs[:, :4], NH)
y_ana, ctx = paper_nets.lstm_forward_aimc(params, xs[:, :4], NH, cfg,
                                          jax.random.fold_in(KEY, 2))
agree = float(jnp.mean((jnp.argmax(y_dig, -1)
                        == jnp.argmax(y_ana, -1)).astype(jnp.float32)))
print(f"\nAIMC inference: next-char agreement with digital = {agree:.0%}")
print(f"CM_* instruction counts for {xs.shape[0]} steps x 4 seqs: "
      f"{ctx.instruction_counts()}")

# ---- the paper's timing/energy exploration (Fig. 10) ------------------------
print("\nanalytical per-inference cost (paper Table II sizes):")
for nh in (256, 512, 750):
    w = lstm_workloads(nh)
    for sysc in (HIGH_POWER, LOW_POWER):
        dig = evaluate(w["dig_1c"], sysc)
        ana = evaluate(w["ana_case1"], sysc)
        s, e = speedup(dig, ana)
        print(f"  n_h={nh:3d} {sysc.name:10s}: digital "
              f"{dig.time_s * 1e6:7.1f}us -> AIMC {ana.time_s * 1e6:6.1f}us "
              f"({s:4.1f}x perf, {e:4.1f}x energy)")
