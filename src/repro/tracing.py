"""Names the program gives its work on the profiler's timeline.

Host spans: `span` marks a stretch of the serving engine's host work
(`runtime/engine.py`) as a `jax.profiler.TraceAnnotation` named
``engine.<name>``, with the request or chunk it serves as keyword ids
(``rid``, ``slot``, ``n``). With no profiler running it costs one object.

Model scopes: the constants below are `jax.named_scope` names for the model
code's parts (`models/transformer.py`, `models/layers.py`) and the engine's
decode closure. They exist only at trace time: each op the closure compiles
to carries its scope path in its metadata (the profiler's ``tf_op``, e.g.
``jit(traced)/while/body/closed_call/layers/while/body/block/attention/...``)
and the compiled code is the same with or without them.

  layers       the `lax.scan` over the stacked blocks; ops under it and under
               no `block` are the scan's own slices of its inputs (weights,
               codes, cache) and its stacking of the outputs
  block        one block's own computation inside the scan body
  aimc_mvm     a programmed crossbar projection: DAC, kernel, ADC
  attention    the attention of one block (decode or prefill)
  kv_write     writing the new keys and values into the cache
  head         the final norm, the unembedding and the argmax
  lane_freeze  the engine's selects that keep frozen decode lanes unchanged:
               the emitted token, and the state of a recurrent model (a
               transformer's frozen K/V rows are dropped under kv_write)
"""

from __future__ import annotations

import jax

LAYERS = "layers"
BLOCK = "block"
AIMC_MVM = "aimc_mvm"
ATTENTION = "attention"
KV_WRITE = "kv_write"
HEAD = "head"
LANE_FREEZE = "lane_freeze"
SCOPES = (LAYERS, BLOCK, AIMC_MVM, ATTENTION, KV_WRITE, HEAD, LANE_FREEZE)

SPAN_PREFIX = "engine."


def span(name: str, **ids):
    """A host span ``engine.<name>`` carrying ``ids`` (a context manager)."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **ids)
