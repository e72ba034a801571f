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

Above MAX_DENSE_ROWS a search of sorted rows (a light pick's per-voxel CDF,
a 2D distribution's marginal and conditional CDFs) reads small lane-major
pivot tables and a few elements of the flat rows (`pick_plan`,
`pivot_tables`, `pivot_search`), never a whole row a lane.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

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


#: the largest pivot table (15 rows x voxels x 16^k columns, float32) a
#: level of the pick may read: a level is one `take_columns` of 15 rows
#: and replaces four dependent scalar gathers of the flat table. Priced by
#: `tools/pick_probe.py` on a v5e, 2^18 lanes clustered by voxel: the
#: take reads 0.671-0.677 ms from tables of 30 KB, 480 KB, 1.9 MB and 7.9 MB
#: alike, one gather of the 16.8 MB flat table 3.48 ms; the whole search of
#: 8,192 rows over 512 voxels 45.4 / 18.0 / 11.2 / 4.65 ms with 0 / 1 / 2 / 3
#: levels. So the budget holds the largest table priced, 7.9 MB: level 2 at
#: 512 voxels. Level 3 there is 126 MB and was not priced.
PIVOT_TABLE_BUDGET_BYTES = 8 << 20

#: pivots a level holds (its 16th is the end of the block above it)
PIVOTS = 15


def pick_plan(n: int, voxels: int) -> tuple:
    """(levels, tail steps) of a search of `voxels` rows of `n` elements
    (a light pick: `n` light rows in each voxel's row): as many 4-bit
    levels as the index has bits for and as keep each level's pivot table
    under PIVOT_TABLE_BUDGET_BYTES, then one binary step of the flat table
    a bit left."""
    bits = (n - 1).bit_length()
    levels = 0
    while 4 * (levels + 1) <= bits and (
        PIVOTS * 4 * voxels * 16**levels <= PIVOT_TABLE_BUDGET_BYTES
    ):
        levels += 1
    return levels, bits - 4 * levels


def pivot_tables(cdf: np.ndarray, levels: int) -> tuple:
    """The host's (V, L) CDF, V sorted rows -> one LANE-MAJOR (15, V * 16^k)
    table a level: column v * 16^k + a holds, for the block `a` the levels above found in
    voxel v's row, the row's elements at the ends of its first 15 sixteenths,
    1.0 past the row's end (every row ends in 1.0 > u: no count moves)."""
    n_vox, n = cdf.shape
    bits = (n - 1).bit_length()
    out = []
    for k in range(levels):
        width = 1 << (bits - 4 * (k + 1))  # elements from one pivot to the next
        pos = (np.arange(16**k)[:, None] * 16 + np.arange(1, PIVOTS + 1)) * width - 1
        piv = np.where(pos < n, cdf[:, np.minimum(pos, n - 1)], np.float32(1.0))
        out.append(np.ascontiguousarray(piv.transpose(2, 0, 1).reshape(PIVOTS, n_vox * 16**k)))
    return tuple(out)


def pivot_search(flat, pivots: tuple, n: int, u, row):
    """In sorted row `row` of `n` elements, stored flat (`flat`, (V * n,))
    beside its `pivot_tables`: the count of the row's elements at or under
    u (u < 1), clamped to n - 1 -> (idx, below, above). A row that ends in
    1.0 > u has its count over the first n - 1 elements, which IS the
    clamped count: its top bits 4 a level (the count of a block's 15
    pivots at or under u, from one take of the level's table at the lane's
    block), the rest bit by bit, one read of the flat row a bit. `below` is
    the largest element read that held and `above` the smallest that
    failed: the row's elements at idx - 1 and at idx (0 and 1.0 past its
    ends), so what they bracket costs no further read."""
    last = n - 1
    levels = len(pivots)
    tail = last.bit_length() - 4 * levels
    idx = jnp.zeros(u.shape, jnp.int32)
    below = jnp.zeros(u.shape, jnp.float32)
    above = jnp.ones(u.shape, jnp.float32)
    block = row
    for k, table in enumerate(pivots):
        piv = take_columns(table, block)  # (15, ...)
        held = u >= piv
        digit = jnp.sum(held, axis=0, dtype=jnp.int32)
        below = jnp.maximum(below, jnp.max(jnp.where(held, piv, 0.0), axis=0))
        above = jnp.minimum(above, jnp.min(jnp.where(held, 1.0, piv), axis=0))
        idx = idx + (digit << (tail + 4 * (levels - 1 - k)))
        block = block * 16 + digit
    base = row * n
    for bit in reversed(range(tail)):
        cand = idx + (1 << bit)
        inside = cand <= last
        probe = flat[base + jnp.minimum(cand, last) - 1]
        ok = inside & (u >= probe)
        idx = jnp.where(ok, cand, idx)
        below = jnp.where(ok, probe, below)
        above = jnp.where(inside & ~ok, probe, above)
    return idx, below, above
