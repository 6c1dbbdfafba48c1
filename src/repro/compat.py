"""The one mesh query the installed JAX (0.9) does not spell publicly.

Call sites use the installed API directly: ``jax.set_mesh`` activates a
mesh, ``jax.shard_map`` maps over it, ``Compiled.cost_analysis()`` returns
a dict. What remains here is ``current_mesh()``: the active *concrete*
mesh, inside and outside ``jit``. ``jax.sharding.get_mesh`` refuses to run
under a trace and ``get_abstract_mesh`` carries no devices, but
``shard_map`` callers (``models/moe.py``, ``core/schedule.py``) and the
engine's buffer commit (``ServeEngine._commit_ambient``) need the devices.
"""

from __future__ import annotations

from jax._src.mesh import get_concrete_mesh


def current_mesh():
    """The mesh set by ``jax.set_mesh``, or None when none is active."""
    m = get_concrete_mesh()
    return None if m is None or m.empty else m
