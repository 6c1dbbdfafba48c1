"""Share of decode lanes that carried a live request, over every decode
step of the window: (n_slots * n_steps - idle_vectors) / (n_slots * n_steps),
from the engine's device-loop counters (`ServeReport`)."""


def read(ctx):
    c = ctx.window.counters
    lanes = ctx.engine["n_slots"] * c.get("n_steps", 0)
    if not lanes:
        return None
    return 100.0 * (lanes - c["idle_vectors"]) / lanes
