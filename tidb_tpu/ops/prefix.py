"""Prefix sums the chip's compiler takes in seconds.

XLA:TPU compiles a flat ``jnp.cumsum`` over a long 1-D integer array
slowly, and the longer the slower: 16 s for 65536 int64 rows, 81 s for
1<<20, 24 s for 1<<20 int32 (the chip's compiler on a described v5e,
tests/test_chip_compile.py). The same sum as [n / 1024, 1024] row sums
plus the running totals of the rows compiles in 2-3 s at 6,000,000 rows.
Integer addition is exact, so the regrouping changes no answer. Every
row-sized prefix sum of the device programs (compaction targets, join
expansion offsets, group ids, top-k cut counts) goes through here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["cumsum"]

_BLOCK = 1024


def cumsum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D integer array; equals
    ``jnp.cumsum(x)``."""
    (n,) = x.shape
    if n <= 4 * _BLOCK:
        return jnp.cumsum(x)
    n_blocks = -(-n // _BLOCK)
    rows = jnp.pad(x, (0, n_blocks * _BLOCK - n)).reshape(n_blocks, _BLOCK)
    within = jnp.cumsum(rows, axis=1)
    totals = within[:, -1]
    before = cumsum(totals) - totals  # exclusive: what precedes each row
    return (within + before[:, None]).reshape(-1)[:n]
