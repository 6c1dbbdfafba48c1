"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before *any* jax
initialization.

Axis semantics (DESIGN.md §5):
  pod    — inter-pod data parallelism (DCN-connected in production)
  data   — in-pod data parallel / FSDP axis
  model  — tensor parallel axis (also: MoE experts, decode KV sequence chunks)

Every axis is ``AxisType.Auto``: shardings are GSPMD hints
(``with_sharding_constraint``, pinned jit in/out shardings) that the
partitioner propagates. ``jax.make_mesh`` defaults to ``Explicit`` axes,
under which those hints become asserts.

`fsdp_axes` returns the tuple of axes the parameter/optimizer shards span in
addition to `model` — on a multi-pod mesh parameters shard over pod+data too,
so 512 chips hold one copy of (param, grad, moments).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for elastic re-shapes / tests (e.g. (1, 1) on CPU)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def fsdp_axes(mesh) -> tuple[str, ...]:
    """Parameter-sharding (FSDP) axes = every non-'model' axis."""
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_axes(mesh) -> tuple[str, ...]:
    """Batch-sharding axes (same set as FSDP for this framework)."""
    return fsdp_axes(mesh)


def axis_size(mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n
