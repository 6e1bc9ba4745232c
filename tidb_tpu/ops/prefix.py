"""Prefix sums and running maxima the chip's compiler takes in seconds.

XLA:TPU compiles a flat ``jnp.cumsum`` over a long 1-D integer array
slowly, and the longer the slower: 16 s for 65536 int64 rows, 81 s for
1<<20, 24 s for 1<<20 int32 (the chip's compiler on a described v5e,
tests/test_chip_compile.py). The same sum as [n / 1024, 1024] row sums
plus the running totals of the rows compiles in 2-3 s at 6,000,000 rows.
Integer addition is exact, so the regrouping changes no answer. Every
row-sized prefix sum of the device programs (compaction targets, join
expansion offsets, group ids, top-k cut counts) goes through here.

The running maximum has the same fault in 64 bits: a flat
``jax.lax.cummax`` of the mesh join's 15,002,430 int64 slots compiles in
122.8 s, the blocked one in 4.3 s (same compiler, same described v5e,
PR 26; flat int32 takes 3.2 s, but the join's carried word is 64 bits
wide). A maximum regroups as exactly as a sum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["cumsum", "cummax"]

_BLOCK = 1024


def _rows(x: jax.Array) -> jax.Array:
    """`x` as [ceil(n / _BLOCK), _BLOCK], zero-padded after its end."""
    n_blocks = -(-x.shape[0] // _BLOCK)
    return jnp.pad(x, (0, n_blocks * _BLOCK - x.shape[0])).reshape(
        n_blocks, _BLOCK)


def cumsum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D integer array; equals
    ``jnp.cumsum(x)``."""
    (n,) = x.shape
    if n <= 4 * _BLOCK:
        return jnp.cumsum(x)
    within = jnp.cumsum(_rows(x), axis=1)
    totals = within[:, -1]
    before = cumsum(totals) - totals  # exclusive: what precedes each row
    return (within + before[:, None]).reshape(-1)[:n]


def cummax(x: jax.Array) -> jax.Array:
    """Inclusive running maximum of a 1-D integer array; equals
    ``jax.lax.cummax(x)``. Blocked like `cumsum`, and for the compile
    time given above (row maxima, then the running maxima of the rows
    before): the padding sits after the last element, so it reaches no
    kept position."""
    (n,) = x.shape
    if n <= 4 * _BLOCK:
        return jax.lax.cummax(x)
    within = jax.lax.cummax(_rows(x), axis=1)
    upto = cummax(within[:, -1])
    before = jnp.concatenate([within[:1, 0], upto[:-1]])  # row 0: itself
    return jnp.maximum(within, before[:, None]).reshape(-1)[:n]
