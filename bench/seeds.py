"""Seed conventions shared by the harness and the reference."""

from __future__ import annotations


def seed_key(seed: int):
    """The JAX key of a seed of any size: `PRNGKey` keeps 32 bits, so the
    next 32 are folded in and seeds past 2**32 stay distinct."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
