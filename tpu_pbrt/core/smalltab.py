"""Dense selects for tiny per-scene tables (lights, materials).

Random gathers on this TPU cost ~10-30 ns per fetched ELEMENT regardless
of table size (profiled: the light/material row fetches in the path
integrator's shading phase were ~1.1 s of a 6 s render window on a
3-light scene). For a table with few rows, a where-sum over a one-hot
row mask is pure dense vector math — bandwidth-bound, orders of
magnitude cheaper — and bit-exact (the sum has one nonzero term).

Capability note: this replaces the implicit `Scene::lights[i]` /
material-pointer indirection of pbrt-v3 (src/core/scene.h,
src/core/primitive.cpp GetMaterial) for the SoA tables; semantics are
identical to `table[idx]`.
"""

from __future__ import annotations

import jax.numpy as jnp

#: tables at or below this many rows use the dense select; above it the
#: native gather wins (dense cost grows linearly with row count)
MAX_DENSE_ROWS = 16


def small_take(table, idx, max_rows: int = MAX_DENSE_ROWS):
    """`table[idx]` with a dense one-hot select when the leading dim is
    tiny. idx may have any shape; trailing table dims broadcast.

    Out-of-range idx is CLAMPED to [0, n-1], matching the native
    `table[idx]` gather's clamp mode on both paths (the one-hot compare
    would otherwise silently return zeros for e.g. -1 sentinels)."""
    n = table.shape[0]
    if n > max_rows:
        return table[idx]
    idx = jnp.clip(jnp.asarray(idx), 0, n - 1)
    oh = idx[..., None] == jnp.arange(n, dtype=idx.dtype)  # (..., n)
    ohx = oh.reshape(oh.shape + (1,) * (table.ndim - 1))
    t = table.reshape((1,) * idx.ndim + table.shape)
    out = jnp.sum(jnp.where(ohx, t, 0), axis=idx.ndim)
    return out.astype(table.dtype)


def small_take_along(row, idx, max_cols: int = MAX_DENSE_ROWS * 2):
    """`take_along_axis(row, idx[..., None], -1)[..., 0]` as a dense
    select over a small LAST axis (e.g. per-voxel light-pick CDF rows)."""
    L = row.shape[-1]
    if L > max_cols:
        return jnp.take_along_axis(row, idx[..., None], axis=-1)[..., 0]
    oh = idx[..., None] == jnp.arange(L, dtype=idx.dtype)
    return jnp.sum(jnp.where(oh, row, 0), axis=-1).astype(row.dtype)


def take_columns(table, idx, lo: int = 0, hi: int = None):
    """Rows lo..hi of a LANE-MAJOR (W, n) table at columns idx -> (hi - lo,
    ...): the fetch for tables above MAX_DENSE_ROWS, one packed row an index
    where `small_take` would go column by column (on the v5e a take along
    axis 1 reads ~2.6 ns an element, a row-major row gather ~33:
    scene/compiler.py, `tri_sh16`). idx is clamped, as `small_take` clamps."""
    return jnp.take(table[lo:hi], idx, axis=1, mode="clip")
