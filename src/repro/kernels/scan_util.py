"""Lane/sublane helpers shared by the chunked scan kernels.

Mosaic has no 1-D vector layout worth using and no cumsum lowering, so
the scan kernels keep per-step quantities as (1, Q) rows or (Q, 1)
columns and move between the two with masked reductions over a (Q, Q)
panel — exact in f32 and the same order of work as the kernels' own
intra-chunk panels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _iotas(q):
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return i, j


def col(row):
    """(1, Q) row -> (Q, 1) column."""
    i, j = _iotas(row.shape[1])
    return jnp.sum(jnp.where(i == j, row, 0.0), axis=1, keepdims=True)


def row(column):
    """(Q, 1) column -> (1, Q) row."""
    i, j = _iotas(column.shape[0])
    return jnp.sum(jnp.where(i == j, column, 0.0), axis=0, keepdims=True)


def cumsum_col(row_vec):
    """Inclusive cumsum of a (1, Q) row, as a (Q, 1) column."""
    i, j = _iotas(row_vec.shape[1])
    return jnp.sum(jnp.where(j <= i, row_vec, 0.0), axis=1, keepdims=True)
