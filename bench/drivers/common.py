"""What a driver hands back to the harness."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Window:
    """One measured window.

    ``served`` maps rid -> (prompt, served tokens, asked tokens) for every
    request the window counts; ``e2e`` holds the end-to-end readings the
    driver took on the host clock; ``counters`` the engine's device-loop
    counts summed over the window; ``spans`` harness-side per-request
    timings (seconds); ``notes`` lines for standard error."""
    served: dict
    e2e: dict
    counters: dict
    spans: dict = dataclasses.field(default_factory=dict)
    notes: list = dataclasses.field(default_factory=list)
    wall_s: float = 0.0


COUNTERS = ("n_steps", "n_prefills", "observed_vectors", "idle_vectors",
            "prefill_pad_vectors")


def add_counters(total: dict, report) -> None:
    for name in COUNTERS:
        total[name] = total.get(name, 0) + int(getattr(report, name))


def expected_tokens(prompt_len: int, max_new: int, max_seq: int) -> int:
    """Tokens the engine owes a request: ``max_new``, unless the KV cache
    (``max_seq`` rows) runs out first."""
    return min(max_new, 1 + max(0, max_seq - prompt_len))
