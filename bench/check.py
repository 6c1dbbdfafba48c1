"""The comparison that decides ``correct``.

Once the window has closed, samples of the requests it finished are drawn
from the seed. The configuration's plain reference runs over them in two
layouts, and each served token is scored by its gap: how far the
reference's logit for it lies below the reference's best logit at that
position.

* Sequences (``check_requests`` of them, the longest among them): a
  request's prompt and served tokens as one sequence, every served token
  scored. The engine quantises each decode call over the 32 slots it
  batches, the reference over one request, so the two differ by the
  crossbar's own quantisation noise: readings ``gap`` (the widest),
  ``gap_mean``, ``gap_p99``, ``mismatch`` (the share ranked otherwise).
* Prefills (``check_prefills`` of them): a request's prompt right-padded
  with token 0 to the engine's ``prompt_pad``, every row in one DAC scale,
  as the engine's prefill quantises it; the first served token is scored at
  the last prompt row: ``first_gap``, ``first_gap_mean``,
  ``first_mismatch``.

The readings named in the configuration's ``check.limits`` are compared;
besides, every request the window counts must have received the tokens it
asked for (``short``: limit 0).

A control (``check.controls``: reference settings one precision below the
configured one, such as int4 crossbar codes or bfloat16 float parts) is put
in the program's place: at every scored position, the token the control
ranks first is scored by the configured reference instead of the served
one, and judged against the same limits. A sound check reads each control
as not correct.
"""

from __future__ import annotations

import importlib

import numpy as np

from bench.drivers.common import expected_tokens
from bench.seeds import seed_key

# rows of one reference call, by layout (bounds the reference's memory)
BLOCK_ROWS = {"sequences": 4, "prefills": 8}


def dims_of(cfg, conf: dict) -> dict:
    """The sizes and settings the reference needs, from the configuration
    as run."""
    aimc = conf["execution"]["aimc"]
    if aimc.get("input_scale", 0.0) != 0.0:
        raise ValueError("the reference models the dynamic DAC scale only")
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim or cfg.d_model // cfg.n_heads,
            "d_ff": cfg.d_ff, "vocab": cfg.vocab,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
            "qkv_bias": cfg.qkv_bias, "bias_std": conf.get("bias_std", 0.0),
            "tie_embeddings": cfg.tie_embeddings,
            "tile_rows": aimc["tile_rows"], "adc_alpha": aimc["adc_alpha"]}


def sample(served: dict, seed: int, n: int, stream: int = 7) -> list[int]:
    """rids of ``n`` finished requests: the one with the most served tokens,
    and the rest drawn from the seed."""
    rids = sorted(r for r, (_, toks, _) in served.items() if toks)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(served[r][1]), -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([int(seed), stream])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + sorted(rest[i] for i in pick)


def short_requests(served: dict, max_seq: int) -> int:
    """Counted requests that did not receive every token they were owed."""
    return sum(1 for prompt, toks, max_new in served.values()
               if len(toks) != expected_tokens(len(prompt), max_new, max_seq))


def sequences(served, rids, length: int):
    """tokens/valid [R, length] of prompt + served[:-1], and the served
    tokens at the positions that predict them (``served_at``, -1 elsewhere)."""
    rows = len(rids)
    tokens = np.zeros((rows, length), np.int32)
    valid = np.zeros((rows, length), bool)
    served_at = np.full((rows, length), -1, np.int32)
    for i, rid in enumerate(rids):
        prompt, toks, _ = served[rid]
        seq = list(prompt) + list(toks[:-1])
        tokens[i, :len(seq)] = seq
        valid[i, :len(seq)] = True
        p = len(prompt)
        served_at[i, p - 1:p - 1 + len(toks)] = toks
    return tokens, valid, served_at


def prefills(served, rids, prompt_pad: int):
    """tokens [R, prompt_pad] of each prompt right-padded with token 0, all
    rows valid (the engine's prefill quantises them together), and the first
    served token at the last prompt row."""
    rows = len(rids)
    tokens = np.zeros((rows, prompt_pad), np.int32)
    valid = np.ones((rows, prompt_pad), bool)
    served_at = np.full((rows, prompt_pad), -1, np.int32)
    for i, rid in enumerate(rids):
        prompt, toks, _ = served[rid]
        tokens[i, :len(prompt)] = prompt
        served_at[i, len(prompt) - 1] = toks[0]
    return tokens, valid, served_at


def _reference(conf: dict):
    return importlib.import_module(f"bench.references.{conf['reference']}")


def score(conf: dict, dims: dict, seed: int, tokens, valid, served_at,
          controls: dict, block: int) -> dict:
    """Gaps under the configured reference, over the scored positions
    (``served_at >= 0``): of the served tokens (``"program"``) and of the
    tokens each control ranks first. Returns name -> 1-D array."""
    import jax.numpy as jnp
    ref = _reference(conf)
    key = seed_key(seed)
    out = {name: [] for name in ("program", *controls)}
    for lo in range(0, tokens.shape[0], block):
        t, v, s = (a[lo:lo + block] for a in (tokens, valid, served_at))
        n = t.shape[0]
        if n < block:           # one shape per layout: pad with idle rows
            pad = ((0, block - n), (0, 0))
            t, v = np.pad(t, pad), np.pad(v, pad)
            s = np.pad(s, pad, constant_values=-1)
        scored = s >= 0
        cands = [np.where(scored, s, 0)]
        for settings in controls.values():
            _, _, top = ref.forward_scores(dims, key, jnp.asarray(t),
                                           jnp.asarray(v),
                                           jnp.asarray(cands[0][None]),
                                           **settings)
            cands.append(np.asarray(top))
        best, picked, _ = ref.forward_scores(dims, key, jnp.asarray(t),
                                             jnp.asarray(v),
                                             jnp.asarray(np.stack(cands)))
        best, picked = np.asarray(best), np.asarray(picked)
        for c, name in enumerate(out):
            out[name].append((best - picked[c])[scored])
    return {name: np.concatenate(g) if g else np.zeros((0,))
            for name, g in out.items()}


def evaluate(conf: dict, mix: dict, dims: dict, seed: int, served: dict,
             engine: dict, controls=()) -> dict:
    """Readings of the program and of each named control, in both layouts:
    name -> readings (and ``sampled``/``scored_tokens`` of the program)."""
    settings = {c: conf["check"]["controls"][c] for c in controls}
    names = ("program", *controls)
    readings = {name: {} for name in names}
    seq = sample(served, seed, int(mix["check_requests"]))
    pre = sample(served, seed, int(mix["check_prefills"]), stream=8)
    layouts = (("", "sequences", seq, sequences(served, seq,
                                                engine["max_seq"])),
               ("first_", "prefills", pre,
                prefills(served, pre, engine["prompt_pad"])))
    scored = 0
    for prefix, layout, rids, arrays in layouts:
        if not rids:
            continue
        gaps = score(conf, dims, seed, *arrays, settings, BLOCK_ROWS[layout])
        scored += gaps["program"].size
        for name in names:
            readings[name] |= _readings(gaps[name], prefix)
    return {"readings": readings, "sampled": {"sequences": seq,
                                              "prefills": pre},
            "scored_tokens": scored}


def verdict(conf: dict, readings: dict, short: int) -> dict:
    """The numbers compared beside their limits, and whether all hold."""
    limits = {name: {"value": readings.get(name), "limit": lim}
              for name, lim in conf["check"]["limits"].items()}
    limits["short"] = {"value": short, "limit": 0}
    ok = bool(conf["check"]["limits"]) and all(
        v["value"] is not None and v["limit"] is not None
        and v["value"] <= v["limit"] for v in limits.values())
    return {"correct": ok, "limits": limits}


def judge(conf: dict, mix: dict, dims: dict, seed: int, served: dict,
          engine: dict, controls=()) -> dict:
    """The program's verdict (``correct``, ``limits``, ``readings``), and,
    under ``controls``, each named control's verdict with the control in
    the program's place."""
    ev = evaluate(conf, mix, dims, seed, served, engine, controls)
    short = short_requests(served, engine["max_seq"])
    out = verdict(conf, ev["readings"]["program"], short)
    out |= {"readings": ev["readings"]["program"], "sampled": ev["sampled"],
            "scored_tokens": ev["scored_tokens"],
            "controls": {c: verdict(conf, ev["readings"][c], short)
                         | {"readings": ev["readings"][c]}
                         for c in controls}}
    return out


def _readings(g, prefix: str = "") -> dict:
    if not g.size:
        return {}
    return {f"{prefix}gap": float(np.max(g)),
            f"{prefix}gap_mean": float(np.mean(g)),
            f"{prefix}gap_p99": float(np.percentile(g, 99)),
            f"{prefix}mismatch": float(np.mean(g > 0))}
