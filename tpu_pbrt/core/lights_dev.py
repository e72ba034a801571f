"""Device-side light sampling: the NEE half of the light transport.

Capability match for pbrt-v3:
- src/lights/point.cpp, spot.cpp, distant.cpp, diffuse.cpp (area),
  infinite.cpp — each light type's Sample_Li / Pdf_Li / Le, lowered to a
  tagged-union SoA row per light (area lights are one row per emissive
  triangle, mirroring pbrt's one-DiffuseAreaLight-per-Triangle).
- src/core/integrator.cpp UniformSampleOneLight light selection (uniform or
  power-weighted via lightdistrib.cpp PowerLightDistribution).
- src/core/light.h VisibilityTester: the caller traces the returned shadow
  ray with bvh_intersect_p.

All functions are batched over rays; light-type dispatch is masked select
(few types, cheap formulas — the expensive part, the shadow ray, is shared).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_pbrt.core.sampling import Distribution2D, uniform_sample_triangle
from tpu_pbrt.core.smalltab import MAX_DENSE_ROWS, small_take, small_take_along, take_columns
from tpu_pbrt.core.vecmath import dot, linear3, normalize
from tpu_pbrt.obs import phases as ph
from tpu_pbrt.scene.compiler import (
    LIGHT_AREA,
    LIGHT_DISTANT,
    LIGHT_GONIO,
    LIGHT_INFINITE,
    LIGHT_POINT,
    LIGHT_PROJECTION,
    LIGHT_SPOT,
)


class LightSample(NamedTuple):
    li: jnp.ndarray  # (R,3) incident radiance (pre-visibility)
    wi: jnp.ndarray  # (R,3) world direction to light
    pdf: jnp.ndarray  # (R,) solid-angle pdf x light-pick pmf
    dist: jnp.ndarray  # (R,) shadow-ray length
    is_delta: jnp.ndarray  # (R,) delta light (no MIS vs BSDF)
    li_idx: jnp.ndarray = None  # (R,) sampled light row (BDPT MIS needs it)


# -- one packed row a light (tables above MAX_DENSE_ROWS) ---------------------
# `dev["light"]["rows"]`, float32 (ROW_WIDTH, lights), lane-major like
# `tri_sh16`: one `take` along axis 1 fetches every field `Sample_Li` reads
# where the dense select would fetch them column by column. An area light's
# nine corner floats and another light's p, dir, cos0, cos1 share columns
# 6..14: no row has both (the compiler writes zeros in the columns of the
# other kind, and `_unpack_row` puts the zeros back).
ROW_TYPE, ROW_L, ROW_AREA, ROW_TWOSIDED, ROW_GEOM, ROW_WIDTH = 0, 1, 4, 5, 6, 15
#: what a hit on an emitter reads of its row: L, area, twosided
ROW_EMIT = (ROW_L, ROW_GEOM)


class LightRow(NamedTuple):
    """The fields of `dev["light"]` that `Sample_Li` and `Sample_Le` read,
    for the rows one index array names."""

    type: jnp.ndarray
    p: jnp.ndarray
    L: jnp.ndarray
    dir: jnp.ndarray
    cos0: jnp.ndarray
    cos1: jnp.ndarray
    twosided: jnp.ndarray
    area: jnp.ndarray
    tri_v: jnp.ndarray  # (..., 3, 3)


def pack_light_rows(lt, tri_v) -> np.ndarray:
    """Host: the packed table of the light table `lt` (numpy columns as the
    compiler casts them) and the per-light corners `tri_v` (L, 3, 3)."""
    n = len(lt["type"])
    rows = np.zeros((ROW_WIDTH, n), np.float32)
    rows[ROW_TYPE] = lt["type"]
    rows[ROW_L : ROW_L + 3] = lt["L"].T
    rows[ROW_AREA] = lt["area"]
    rows[ROW_TWOSIDED] = lt["twosided"]
    other = np.concatenate([lt["p"], lt["dir"], lt["cos0"][:, None], lt["cos1"][:, None]], axis=1)
    geom = np.where((lt["type"] == LIGHT_AREA)[:, None], np.asarray(tri_v).reshape(n, 9), np.pad(other, ((0, 0), (0, 1))))
    rows[ROW_GEOM:] = geom.T
    return rows


def _unpack_row(r) -> LightRow:
    """(ROW_WIDTH, ...) fetched rows -> their fields, as `dev["light"]` holds them."""
    col = lambda a, b: jnp.moveaxis(r[a:b], 0, -1)  # noqa: E731
    ltype = r[ROW_TYPE].astype(jnp.int32)
    is_area = ltype == LIGHT_AREA
    geom = col(ROW_GEOM, ROW_WIDTH)
    other = jnp.where(is_area[..., None], 0.0, geom)
    tri_v = jnp.where(is_area[..., None], geom, 0.0).reshape(geom.shape[:-1] + (3, 3))
    return LightRow(
        type=ltype, p=other[..., 0:3], L=col(ROW_L, ROW_L + 3), dir=other[..., 3:6],
        cos0=other[..., 6], cos1=other[..., 7], twosided=r[ROW_TWOSIDED].astype(jnp.int32),
        area=r[ROW_AREA], tri_v=tri_v,
    )


def _light_fields(dev, li_idx):
    """What `Sample_Li` and `Sample_Le` read of light rows li_idx ->
    (type, p, L, dir, cos0, cos1, twosided, area, corners, row): ONE packed
    row where the table has them (`row`, else None), the dense select a
    column at a time where it has not; `corners()` fetches the (..., 3, 3)
    triangle when the caller comes to it."""
    lt = dev["light"]
    if "rows" in lt:
        row = _unpack_row(take_columns(lt["rows"], li_idx))
        return (*row[:8], lambda: row.tri_v, row)
    ltype = small_take(lt["type"], li_idx)
    lp = small_take(lt["p"], li_idx)
    lL = small_take(lt["L"], li_idx)
    ldir = small_take(lt["dir"], li_idx)
    cos0 = small_take(lt["cos0"], li_idx)
    cos1 = small_take(lt["cos1"], li_idx)
    tri = small_take(lt["tri"], li_idx)
    twosided = small_take(lt["twosided"], li_idx)
    area = small_take(lt["area"], li_idx)

    def corners():
        if "tri_v" in lt:
            return small_take(lt["tri_v"], li_idx)  # (R,3,3) dense select
        return dev["tri_verts"][jnp.maximum(tri, 0)]

    return ltype, lp, lL, ldir, cos0, cos1, twosided, area, corners, None


def _spot_falloff(cos_w, cos_falloff_start, cos_total_width):
    d = jnp.clip(
        (cos_w - cos_total_width) / jnp.maximum(cos_falloff_start - cos_total_width, 1e-9),
        0.0,
        1.0,
    )
    return jnp.where(cos_w < cos_total_width, 0.0, jnp.where(cos_w > cos_falloff_start, 1.0, d * d * d * d))


def env_lookup(dev, d_world):
    """InfiniteAreaLight::Le for directions (bilinear lat-long lookup)."""
    env = dev["envmap"]
    h, w = env.shape[:2]
    wl = normalize(linear3(dev["env_w2l"], d_world))
    phi = jnp.arctan2(wl[..., 1], wl[..., 0])
    phi = jnp.where(phi < 0.0, phi + 2.0 * jnp.pi, phi)
    theta = jnp.arccos(jnp.clip(wl[..., 2], -1.0, 1.0))
    u = phi * (0.5 / jnp.pi)
    v = theta / jnp.pi
    x = u * w - 0.5
    y = v * h - 0.5
    x0 = jnp.floor(x).astype(jnp.int32)
    y0 = jnp.floor(y).astype(jnp.int32)
    fx = x - x0
    fy = y - y0
    x0w = jnp.mod(x0, w)
    x1w = jnp.mod(x0 + 1, w)
    y0c = jnp.clip(y0, 0, h - 1)
    y1c = jnp.clip(y0 + 1, 0, h - 1)
    c00 = env[y0c, x0w]
    c10 = env[y0c, x1w]
    c01 = env[y1c, x0w]
    c11 = env[y1c, x1w]
    fx = fx[..., None]
    fy = fy[..., None]
    return (c00 * (1 - fx) + c10 * fx) * (1 - fy) + (c01 * (1 - fx) + c11 * fx) * fy


def env_pdf(dev, d_world):
    """Solid-angle pdf of sampling d via the env importance map."""
    distr: Distribution2D = dev["env_distr"]
    wl = normalize(linear3(dev["env_w2l"], d_world))
    phi = jnp.arctan2(wl[..., 1], wl[..., 0])
    phi = jnp.where(phi < 0.0, phi + 2.0 * jnp.pi, phi)
    theta = jnp.arccos(jnp.clip(wl[..., 2], -1.0, 1.0))
    sin_t = jnp.sin(theta)
    p_uv = distr.pdf(phi * (0.5 / jnp.pi), theta / jnp.pi)
    return jnp.where(sin_t > 1e-7, p_uv / (2.0 * jnp.pi * jnp.pi * jnp.maximum(sin_t, 1e-9)), 0.0)


def _env_sample(dev, u1, u2):
    """Sample direction from the env map distribution. Returns (wi, pdf, li)."""
    distr: Distribution2D = dev["env_distr"]
    (u, v), pdf_uv = distr.sample_continuous(u1, u2)
    theta = v * jnp.pi
    phi = u * 2.0 * jnp.pi
    sin_t = jnp.sin(theta)
    wl = jnp.stack([sin_t * jnp.cos(phi), sin_t * jnp.sin(phi), jnp.cos(theta)], axis=-1)
    # light-to-world: env_w2l is world->light rotation, transpose back
    wi = linear3(dev["env_w2l"].T, wl)
    pdf = jnp.where(sin_t > 1e-7, pdf_uv / (2.0 * jnp.pi * jnp.pi * jnp.maximum(sin_t, 1e-9)), 0.0)
    li = env_lookup(dev, wi)
    return wi, pdf, li


def sample_triangle_point(tv, u1, u2):
    """Uniform point + geometric normal on (…,3,3) triangles — shared by
    Sample_Li, Sample_Le and BDPT's resample bookkeeping so the pdfs stay
    bit-identical across estimators."""
    b0, b1 = uniform_sample_triangle(u1, u2)
    p = (
        b0[..., None] * tv[..., 0, :]
        + b1[..., None] * tv[..., 1, :]
        + (1.0 - b0 - b1)[..., None] * tv[..., 2, :]
    )
    n = jnp.cross(tv[..., 1, :] - tv[..., 0, :], tv[..., 2, :] - tv[..., 0, :])
    n = n / jnp.maximum(jnp.linalg.norm(n, axis=-1, keepdims=True), 1e-20)
    return p, n


def triangle_normal(tv):
    """Geometric normal of (…,3,3) triangles (shared helper)."""
    n = jnp.cross(tv[..., 1, :] - tv[..., 0, :], tv[..., 2, :] - tv[..., 0, :])
    return n / jnp.maximum(jnp.linalg.norm(n, axis=-1, keepdims=True), 1e-20)


def _light_map_scale(dev, lt, li_idx, w_from_light, is_gonio, is_proj, row=None):
    """Image-modulated angular intensity of goniometric/projection lights
    (goniometric.h Scale, projection.cpp Projection). w_from_light is the
    world direction FROM the light toward the shading point; each row
    carries its world-to-light rotation and its (offset, w, h) window into
    the shared light atlas. Clamp-filtered bilinear lookup with per-row
    traced extents."""
    atlas = dev["light_atlas"]
    w2l = small_take(lt["w2l"], li_idx).reshape(li_idx.shape + (3, 3))
    img = small_take(lt["img"], li_idx)  # (..., 3): offset, width, height
    off, iw, ih = img[..., 0], img[..., 1], img[..., 2]
    dl = jnp.einsum("...ij,...j->...i", w2l, w_from_light)
    dl = normalize(dl)

    # goniometric: lat-long about the Y axis — pbrt goniometric.h Scale()
    # swaps y/z before SphericalTheta/Phi, so theta comes from the
    # light-space Y component and phi from (x, z)
    theta = jnp.arccos(jnp.clip(dl[..., 1], -1.0, 1.0))
    phi = jnp.arctan2(dl[..., 2], dl[..., 0])
    phi = jnp.where(phi < 0, phi + 2 * jnp.pi, phi)
    u_g = phi / (2 * jnp.pi)
    v_g = theta / jnp.pi

    # projection: perspective divide into the fov screen window
    tan_half = small_take(lt["cos0"], li_idx) if row is None else row.cos0
    aspect = small_take(lt["cos1"], li_idx) if row is None else row.cos1
    z = dl[..., 2]
    inside_z = z > 1e-3
    zs = jnp.where(inside_z, z, 1.0)
    sx = dl[..., 0] / (zs * jnp.maximum(tan_half, 1e-6))
    sy = dl[..., 1] / (zs * jnp.maximum(tan_half, 1e-6))
    u_p = (sx / jnp.maximum(aspect, 1.0) + 1.0) * 0.5
    v_p = (sy * jnp.minimum(aspect, 1.0) + 1.0) * 0.5
    in_win = inside_z & (u_p >= 0) & (u_p < 1) & (v_p >= 0) & (v_p < 1)

    u = jnp.where(is_proj, u_p, u_g)
    v = jnp.where(is_proj, v_p, v_g)

    x = u * iw - 0.5
    y = v * ih - 0.5
    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    fx = x - x0
    fy = y - y0

    def tap(ix, iy):
        ix = jnp.clip(ix.astype(jnp.int32), 0, jnp.maximum(iw - 1, 0))
        iy = jnp.clip(iy.astype(jnp.int32), 0, jnp.maximum(ih - 1, 0))
        return atlas[jnp.maximum(off, 0) + iy * iw + ix]

    c = (
        tap(x0, y0) * ((1 - fx) * (1 - fy))[..., None]
        + tap(x0 + 1, y0) * (fx * (1 - fy))[..., None]
        + tap(x0, y0 + 1) * ((1 - fx) * fy)[..., None]
        + tap(x0 + 1, y0 + 1) * (fx * fy)[..., None]
    )
    use = (is_gonio | (is_proj & in_win)) & (off >= 0)
    return jnp.where(use[..., None], c, jnp.where(is_proj[..., None], 0.0, 1.0))


def sample_light_rows(dev, li_idx, ref_p, u1, u2) -> LightSample:
    """Sample_Li for explicit light rows li_idx (R,) — no pick pmf folded."""
    with jax.named_scope(ph.LIGHT_SAMPLE):
        return _sample_light_rows(dev, li_idx, ref_p, u1, u2)


def _sample_light_rows(dev, li_idx, ref_p, u1, u2) -> LightSample:
    lt = dev["light"]
    ltype, lp, lL, ldir, cos0, cos1, twosided, area, corners, row = _light_fields(dev, li_idx)
    wr = dev["world_radius"]

    # -- point / spot -----------------------------------------------------
    to_l = lp - ref_p
    d2 = jnp.maximum(jnp.sum(to_l * to_l, axis=-1), 1e-20)
    dist_pt = jnp.sqrt(d2)
    wi_pt = to_l / dist_pt[..., None]
    li_pt = lL / d2[..., None]
    fall = _spot_falloff(dot(-wi_pt, ldir), cos0, cos1)
    li_spot = li_pt * fall[..., None]

    # -- distant ----------------------------------------------------------
    wi_dist = ldir
    li_dist = lL
    dist_dist = jnp.full_like(dist_pt, 2.0) * wr

    # -- area (triangle) --------------------------------------------------
    tv = corners()
    p_l, n_l = sample_triangle_point(tv, u1, u2)
    to_a = p_l - ref_p
    d2a = jnp.maximum(jnp.sum(to_a * to_a, axis=-1), 1e-12)
    dist_a = jnp.sqrt(d2a)
    wi_a = to_a / dist_a[..., None]
    cos_l = dot(n_l, -wi_a)
    emits = (cos_l > 0.0) | (twosided > 0)
    li_a = jnp.where(emits[..., None], lL, 0.0)
    # area pdf -> solid angle
    pdf_a = d2a / jnp.maximum(jnp.abs(cos_l) * area, 1e-12)

    # -- infinite ---------------------------------------------------------
    if "envmap" in dev:
        wi_env, pdf_env, li_env = _env_sample(dev, u1, u2)
        dist_env = jnp.full_like(dist_pt, 2.0) * wr
    else:
        wi_env = wi_dist
        pdf_env = jnp.zeros_like(dist_pt)
        li_env = jnp.zeros_like(lL)
        dist_env = dist_dist

    # -- goniometric / projection (image-modulated point intensity) -------
    is_gonio = ltype == LIGHT_GONIO
    is_proj = ltype == LIGHT_PROJECTION
    if "light_atlas" in dev:
        scale_img = _light_map_scale(dev, lt, li_idx, -wi_pt, is_gonio, is_proj, row)
        li_gonio = li_pt * scale_img
    else:
        li_gonio = li_pt

    # -- select by type ---------------------------------------------------
    is_pt = ltype == LIGHT_POINT
    is_spot = ltype == LIGHT_SPOT
    is_distant = ltype == LIGHT_DISTANT
    is_area = ltype == LIGHT_AREA
    is_env = ltype == LIGHT_INFINITE

    wi = jnp.where(is_area[..., None], wi_a, wi_pt)
    wi = jnp.where(is_distant[..., None], wi_dist, wi)
    wi = jnp.where(is_env[..., None], wi_env, wi)
    li = jnp.where(is_area[..., None], li_a, li_pt)
    li = jnp.where(is_spot[..., None], li_spot, li)
    li = jnp.where((is_gonio | is_proj)[..., None], li_gonio, li)
    li = jnp.where(is_distant[..., None], li_dist, li)
    li = jnp.where(is_env[..., None], li_env, li)
    pdf = jnp.where(is_area, pdf_a, 1.0)
    pdf = jnp.where(is_env, pdf_env, pdf)
    dist = jnp.where(is_area, dist_a, dist_pt)
    dist = jnp.where(is_distant | is_env, dist_env, dist)
    is_delta = is_pt | is_spot | is_distant | is_gonio | is_proj

    li = jnp.where((pdf > 0.0)[..., None], li, 0.0)
    return LightSample(li, wi, pdf, dist, is_delta, li_idx)


#: the largest pivot table (15 rows x voxels x 16^k columns, float32) a
#: level of the pick may read: a level is one `take_columns` of 15 rows
#: and replaces four dependent scalar gathers of the flat table. Priced by
#: `tools/pick_probe.py` on a v5e (PR 38), 2^18 lanes clustered by voxel: the
#: take reads 0.671-0.677 ms from tables of 30 KB, 480 KB, 1.9 MB and 7.9 MB
#: alike, one gather of the 16.8 MB flat table 3.48 ms; the whole search of
#: 8,192 rows over 512 voxels 45.4 / 18.0 / 11.2 / 4.65 ms with 0 / 1 / 2 / 3
#: levels. So the budget holds the largest table priced, 7.9 MB: level 2 at
#: 512 voxels. Level 3 there is 126 MB and was not priced.
PIVOT_TABLE_BUDGET_BYTES = 8 << 20

#: pivots a level holds (its 16th is the end of the block above it)
PIVOTS = 15


def pick_plan(n: int, voxels: int) -> tuple:
    """(levels, tail steps) of a search of `n` light rows over `voxels`
    rows: as many 4-bit levels as the index has bits for and as keep each
    level's pivot table under PIVOT_TABLE_BUDGET_BYTES, then one binary
    step of the flat table a bit left."""
    bits = (n - 1).bit_length()
    levels = 0
    while 4 * (levels + 1) <= bits and (
        PIVOTS * 4 * voxels * 16**levels <= PIVOT_TABLE_BUDGET_BYTES
    ):
        levels += 1
    return levels, bits - 4 * levels


def pivot_tables(cdf: np.ndarray, levels: int) -> tuple:
    """The host's (V, L) CDF -> one LANE-MAJOR (15, V * 16^k) table a level:
    column v * 16^k + a holds, for the block `a` the levels above found in
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


class SpatialLightDistribution(NamedTuple):
    """lightdistrib.cpp SpatialLightDistribution, precomputed dense.

    pbrt voxelizes the scene and builds a per-voxel light Distribution1D
    LAZILY in a lock-free hash (64-entry packed keys); the TPU-shaped
    equivalent precomputes every voxel's CDF at scene compile into one
    dense (V, L) table, no hashing and no laziness. The per-voxel
    importance is estimated at the voxel center (pbrt Monte-Carlos 128
    points per voxel; documented simplification).

    Up to MAX_DENSE_ROWS lights a pick gathers its voxel's row and counts
    along it. Above, the table is stored FLAT, row after row, beside small
    lane-major pivot tables (`pick_plan`, `pivot_tables`), and all are
    arguments of the program (`dev["light_pick"]`, `bound`): a pick finds
    4 bits of its index a level from one take of 15 pivots, then the rest
    one read of the flat table a bit, and the pmf is the difference of
    the two elements that bracket the index, read where they were found;
    no value of lanes x lights exists."""

    cdf: jnp.ndarray  # (V, L) inclusive per-voxel CDF; (V * L,) above MAX_DENSE_ROWS
    mean_pmf: jnp.ndarray  # (L,) scene-wide marginal (positionless fallback)
    lo: jnp.ndarray  # (3,)
    inv_cs: jnp.ndarray  # (3,)
    res: tuple  # STATIC (nx, ny, nz)
    n: int = 0  # STATIC light rows L
    pivots: tuple = ()  # (15, V * 16^k) a level k of the search; () at or under MAX_DENSE_ROWS

    @staticmethod
    def build(cdf, mean_pmf, lo, inv_cs, res) -> "SpatialLightDistribution":
        """From the host's (V, L) float32 table, each row ending in 1.0."""
        n = cdf.shape[-1]
        searched = n > MAX_DENSE_ROWS
        levels = pick_plan(n, cdf.shape[0])[0] if searched else 0
        return SpatialLightDistribution(
            cdf=jnp.asarray(cdf.reshape(-1) if searched else cdf),
            mean_pmf=jnp.asarray(mean_pmf),
            lo=jnp.asarray(lo, jnp.float32),
            inv_cs=jnp.asarray(inv_cs, jnp.float32),
            res=res,
            n=n,
            pivots=tuple(jnp.asarray(t) for t in pivot_tables(cdf, levels)),
        )

    def tables(self) -> dict:
        """The arrays, for `dev["light_pick"]`."""
        return {"cdf": self.cdf, "mean_pmf": self.mean_pmf, "lo": self.lo, "inv_cs": self.inv_cs,
                "pivots": self.pivots}

    def bound(self, dev) -> "SpatialLightDistribution":
        """With the tables the program was handed, where it was handed them."""
        return self._replace(**dev["light_pick"]) if "light_pick" in dev else self

    @property
    def plan(self) -> tuple:
        """(levels, tail steps) the search takes: the levels are the pivot
        tables it holds; (0, 0) where the row is gathered whole."""
        if self.n <= MAX_DENSE_ROWS:
            return 0, 0
        return len(self.pivots), (self.n - 1).bit_length() - 4 * len(self.pivots)

    @property
    def table_reads(self) -> int:
        """Elements of the tables a pick reads: its voxel's whole row where
        the row is gathered, else 15 pivots a level and one a tail step."""
        levels, tail = self.plan
        return PIVOTS * levels + tail if self.n > MAX_DENSE_ROWS else self.n

    def _voxel(self, p):
        nx, ny, nz = self.res
        v = jnp.floor((p - self.lo) * self.inv_cs).astype(jnp.int32)
        v = jnp.clip(v, 0, jnp.asarray([nx - 1, ny - 1, nz - 1], jnp.int32))
        return v[..., 0] + nx * (v[..., 1] + ny * v[..., 2])

    def _search(self, u, voxel):
        """The count of the row's elements at or under u (u < 1), clamped
        to L - 1, and the pmf there. A row ends in 1.0 > u, so the count
        over its first L - 1 elements IS the clamped count: its top bits
        4 a level (the count of a block's 15 pivots at or under u, from
        one take of the level's table at the lane's block), the rest bit
        by bit, one read of the flat table a bit. `below` is the largest
        element read that held and `above` the smallest that failed: the
        row's elements at idx - 1 and at idx (0 and 1.0 past its ends),
        so the pmf costs no further read."""
        last = self.n - 1
        levels, tail = self.plan
        idx = jnp.zeros(u.shape, jnp.int32)
        below = jnp.zeros(u.shape, jnp.float32)
        above = jnp.ones(u.shape, jnp.float32)
        block = voxel
        for k, table in enumerate(self.pivots):
            piv = take_columns(table, block)  # (15, ...)
            held = u >= piv
            digit = jnp.sum(held, axis=0, dtype=jnp.int32)
            below = jnp.maximum(below, jnp.max(jnp.where(held, piv, 0.0), axis=0))
            above = jnp.minimum(above, jnp.min(jnp.where(held, 1.0, piv), axis=0))
            idx = idx + (digit << (tail + 4 * (levels - 1 - k)))
            block = block * 16 + digit
        base = voxel * self.n
        for bit in reversed(range(tail)):
            cand = idx + (1 << bit)
            inside = cand <= last
            probe = self.cdf[base + jnp.minimum(cand, last) - 1]
            ok = inside & (u >= probe)
            idx = jnp.where(ok, cand, idx)
            below = jnp.where(ok, probe, below)
            above = jnp.where(inside & ~ok, probe, above)
        return idx, jnp.maximum(above - below, 1e-12)

    def sample_discrete_at(self, u, p):
        if self.n > MAX_DENSE_ROWS:
            return self._search(u, self._voxel(p))
        row = self.cdf[self._voxel(p)]  # (..., L)
        idx = jnp.sum((u[..., None] >= row).astype(jnp.int32), axis=-1)
        idx = jnp.minimum(idx, row.shape[-1] - 1)
        prev = jnp.where(
            idx > 0, small_take_along(row, jnp.maximum(idx - 1, 0)), 0.0
        )
        pmf = small_take_along(row, idx) - prev
        return idx, jnp.maximum(pmf, 1e-12)

    def discrete_pdf_at(self, idx, p):
        if self.n > MAX_DENSE_ROWS:  # two elements of the row
            at = self._voxel(p) * self.n + jnp.clip(idx, 0, self.n - 1)
            prev = jnp.where(idx > 0, self.cdf[jnp.maximum(at - 1, 0)], 0.0)
            return jnp.maximum(self.cdf[at] - prev, 1e-12)
        row = self.cdf[self._voxel(p)]
        idx = jnp.clip(idx, 0, row.shape[-1] - 1)
        prev = jnp.where(
            idx > 0, small_take_along(row, jnp.maximum(idx - 1, 0)), 0.0
        )
        return jnp.maximum(small_take_along(row, idx) - prev, 1e-12)


def _bound(dev, light_distr):
    if isinstance(light_distr, SpatialLightDistribution):
        return light_distr.bound(dev)
    return light_distr


def pick_reads(dev, light_distr) -> int:
    """STATIC: table elements one lane's `sample_one_light` reads where a
    light is one packed row: of the distribution's tables to pick (15
    pivots a level of the search and one element a tail step; a uniform
    pick reads none), then the row."""
    if isinstance(light_distr, SpatialLightDistribution):
        picked = light_distr.table_reads
    else:  # `Distribution1D.sample_discrete`: searchsorted, then func[offset]
        picked = 0 if light_distr is None else (dev["light"]["type"].shape[0] + 1).bit_length() + 1
    return picked + ROW_WIDTH


def emit_reads(dev, light_distr) -> int:
    """STATIC: table elements one lane's `emitted_radiance` and
    `emitted_pdf` read for the MIS weight of a hit on an emitter, where a
    light is one packed row: two of a searched table, the row's ROW_EMIT."""
    if isinstance(light_distr, SpatialLightDistribution):
        pdf = 2
    else:
        pdf = 0 if light_distr is None else 1
    return pdf + ROW_EMIT[1] - ROW_EMIT[0]


def sample_one_light(dev, light_distr, ref_p, u_pick, u1, u2) -> LightSample:
    """UniformSampleOneLight's light-selection + Sample_Li, batched.

    light_distr: None for uniform pick, a Distribution1D (power), or a
    SpatialLightDistribution (position-dependent pick).
    Returns pdf already including the pick pmf (contribution / pdf is then
    the single-light estimator of the sum over lights)."""
    lt = dev["light"]
    n = lt["type"].shape[0]
    with jax.named_scope(ph.LIGHT_PICK):
        light_distr = _bound(dev, light_distr)
        if light_distr is None:
            li_idx = jnp.minimum((u_pick * n).astype(jnp.int32), n - 1)
            pick_pmf = jnp.full(u_pick.shape, 1.0 / n, jnp.float32)
        elif isinstance(light_distr, SpatialLightDistribution):
            li_idx, pick_pmf = light_distr.sample_discrete_at(u_pick, ref_p)
        else:
            li_idx, pick_pmf = light_distr.sample_discrete(u_pick)
    ls = sample_light_rows(dev, li_idx, ref_p, u1, u2)
    return LightSample(ls.li, ls.wi, ls.pdf * pick_pmf, ls.dist, ls.is_delta, li_idx)


def emitted_pdf(dev, light_distr, ref_p, hit_p, light_idx, n_l):
    """Solid-angle pdf (incl. pick pmf) of light-sampling the point hit_p on
    area light `light_idx` from ref_p."""
    with jax.named_scope(ph.LIGHT_PDF):
        return _emitted_pdf(dev, _bound(dev, light_distr), ref_p, hit_p, light_idx, n_l)


def _emit_columns(lt, idx):
    """L (..., 3), area, twosided of light rows idx: what a hit on an
    emitter reads, in one fetch of the packed row's ROW_EMIT columns."""
    r = take_columns(lt["rows"], idx, *ROW_EMIT)
    return jnp.moveaxis(r[0:3], 0, -1), r[ROW_AREA - ROW_L], r[ROW_TWOSIDED - ROW_L]


def _emitted_pdf(dev, light_distr, ref_p, hit_p, light_idx, n_l):
    lt = dev["light"]
    n = lt["type"].shape[0]
    if "rows" in lt:
        area = _emit_columns(lt, jnp.maximum(light_idx, 0))[1]
    else:
        area = small_take(lt["area"], jnp.maximum(light_idx, 0))
    to_h = hit_p - ref_p
    d2 = jnp.maximum(jnp.sum(to_h * to_h, axis=-1), 1e-12)
    wi = to_h / jnp.sqrt(d2)[..., None]
    cos_l = jnp.abs(dot(n_l, -wi))
    pdf_sa = d2 / jnp.maximum(cos_l * area, 1e-12)
    if light_distr is None:
        pmf = 1.0 / n
    elif isinstance(light_distr, SpatialLightDistribution):
        pmf = light_distr.discrete_pdf_at(jnp.maximum(light_idx, 0), ref_p)
    else:
        pmf = light_distr.discrete_pdf(jnp.maximum(light_idx, 0))
    return pdf_sa * pmf


def infinite_pdf(dev, light_distr, wi, ref_p=None):
    """Pdf_Li x pick pmf for escaped (BSDF-sampled) rays toward the env.
    ref_p: scattering position (needed for the spatial strategy's pick
    pmf; None falls back to the scene-wide marginal)."""
    lt = dev["light"]
    n = lt["type"].shape[0]
    if "envmap" not in dev:
        return jnp.zeros(wi.shape[:-1], jnp.float32)
    light_distr = _bound(dev, light_distr)
    p = env_pdf(dev, wi)
    is_env = lt["type"] == LIGHT_INFINITE
    if light_distr is None:
        pmf = jnp.sum(is_env.astype(jnp.float32)) / n
    elif isinstance(light_distr, SpatialLightDistribution):
        idx = jnp.argmax(is_env)
        if ref_p is None:
            pmf = light_distr.mean_pmf[idx]
        else:
            pmf = light_distr.discrete_pdf_at(
                jnp.broadcast_to(idx, wi.shape[:-1]), ref_p
            )
    else:
        idx = jnp.argmax(is_env)
        pmf = light_distr.discrete_pdf(idx)
    return p * pmf


class LeSample(NamedTuple):
    """One sampled emission ray per lane (Light::Sample_Le, light.h)."""

    li_idx: jnp.ndarray  # (R,) light row
    pmf: jnp.ndarray  # (R,) pick pmf
    p: jnp.ndarray  # (R,3) emission origin
    n: jnp.ndarray  # (R,3) emission normal (light forward dir for deltas)
    d: jnp.ndarray  # (R,3) emission direction
    le: jnp.ndarray  # (R,3) emitted radiance/intensity
    pdf_pos: jnp.ndarray  # (R,) area-measure position pdf (1 for deltas)
    pdf_dir: jnp.ndarray  # (R,) solid-angle direction pdf
    is_delta: jnp.ndarray  # (R,) delta-position light (point/spot)
    supported: jnp.ndarray  # (R,) light type has a BDPT emission model


def sample_le(dev, light_distr, u_pick, up1, up2, ud1, ud2) -> LeSample:
    """Light::Sample_Le for BDPT/SPPM light subpaths (point.cpp:169,
    spot.cpp:94, diffuse.cpp:124, distant.cpp:59, infinite.cpp:129
    Sample_Le), batched with masked type dispatch. Distant/infinite
    lights emit from the scene-spanning disk behind their direction
    (VERDICT r4 #10)."""
    from tpu_pbrt.core.sampling import (
        concentric_sample_disk,
        cosine_sample_hemisphere,
        uniform_sample_sphere,
    )
    from tpu_pbrt.core.vecmath import coordinate_system

    lt = dev["light"]
    n_lights = lt["type"].shape[0]
    light_distr = _bound(dev, light_distr)
    if light_distr is None:
        li_idx = jnp.minimum((u_pick * n_lights).astype(jnp.int32), n_lights - 1)
        pmf = jnp.full(u_pick.shape, 1.0 / n_lights, jnp.float32)
    elif isinstance(light_distr, SpatialLightDistribution):
        # emission has no receiver position; pick by the scene marginal
        cdf = jnp.cumsum(light_distr.mean_pmf)
        if n_lights > MAX_DENSE_ROWS:
            count = jnp.searchsorted(cdf, u_pick, side="right").astype(jnp.int32)
        else:
            count = jnp.sum((u_pick[..., None] >= cdf).astype(jnp.int32), -1)
        li_idx = jnp.minimum(count, n_lights - 1)
        pmf = jnp.maximum(small_take(light_distr.mean_pmf, li_idx), 1e-12)
    else:
        li_idx, pmf = light_distr.sample_discrete(u_pick)
    ltype, lp, lL, ldir, cos0, cos1, twosided, area, corners, row = _light_fields(dev, li_idx)

    # -- point: uniform sphere -------------------------------------------
    d_pt = uniform_sample_sphere(ud1, ud2)
    pdf_dir_pt = jnp.full_like(ud1, 1.0 / (4.0 * jnp.pi))

    # -- spot: uniform cone of the total width (spot.cpp Sample_Le) ------
    from tpu_pbrt.core.sampling import uniform_cone_pdf, uniform_sample_cone

    d_cone = uniform_sample_cone(ud1, ud2, cos1)  # local frame, +z axis
    s1, s2 = coordinate_system(ldir)
    d_spot = d_cone[..., 0:1] * s1 + d_cone[..., 1:2] * s2 + d_cone[..., 2:3] * ldir
    pdf_dir_spot = uniform_cone_pdf(cos1)
    fall = _spot_falloff(d_cone[..., 2], cos0, cos1)
    le_spot = lL * fall[..., None]

    # -- area: uniform point on the triangle + cosine hemisphere ---------
    # twosided lights pick the emission side with a remapped ud1 and halve
    # the direction pdf (diffuse.cpp Sample_Le / Pdf_Le)
    tv = corners()
    p_a, n_front = sample_triangle_point(tv, up1, up2)
    two = twosided > 0
    flip = two & (ud1 >= 0.5)
    ud1_a = jnp.where(two, jnp.minimum(ud1 * 2.0 % 1.0, 0.999999), ud1)
    n_a = jnp.where(flip[..., None], -n_front, n_front)
    d_loc = cosine_sample_hemisphere(ud1_a, ud2)
    t1, t2 = coordinate_system(n_a)
    d_a = d_loc[..., 0:1] * t1 + d_loc[..., 1:2] * t2 + d_loc[..., 2:3] * n_a
    pdf_dir_a = jnp.abs(d_loc[..., 2]) / jnp.pi
    pdf_dir_a = jnp.where(two, pdf_dir_a * 0.5, pdf_dir_a)
    pdf_pos_a = 1.0 / jnp.maximum(area, 1e-20)

    is_pt = ltype == LIGHT_POINT
    is_spot = ltype == LIGHT_SPOT
    is_area = ltype == LIGHT_AREA
    # goniometric/projection photons: point-position emission over the
    # sphere with the image-modulated intensity (goniometric.cpp /
    # projection.cpp Sample_Le; projection directions outside the fov
    # window carry zero and are wasted, as in the reference's cone)
    is_img = (ltype == LIGHT_GONIO) | (ltype == LIGHT_PROJECTION)
    is_distant = ltype == LIGHT_DISTANT
    is_env = ltype == LIGHT_INFINITE

    # -- distant (distant.cpp Sample_Le): ldir points TOWARD the light
    # (compiler stores from - to), so photons travel along -ldir from a
    # world-spanning disk offset a radius toward the light;
    # pdf_pos = 1/(pi r^2), pdf_dir = 1 (delta direction)
    wr = dev["world_radius"]
    wc = dev["world_center"]
    dx_d, dy_d = concentric_sample_disk(up1, up2)
    v1d, v2d = coordinate_system(ldir)
    p_disk = wc + wr * (dx_d[..., None] * v1d + dy_d[..., None] * v2d)
    p_dist = p_disk + ldir * wr
    pdf_pos_dist = 1.0 / (jnp.pi * wr * wr)

    # -- infinite (infinite.cpp Sample_Le): direction from the envmap
    # importance distribution (PHOTONS travel -wi), origin on the
    # tangent disk behind that direction
    if "envmap" in dev:
        wi_e, pdf_e, le_e = _env_sample(dev, ud1, ud2)
        d_env = -wi_e
        dx_e, dy_e = concentric_sample_disk(up1, up2)
        v1e, v2e = coordinate_system(d_env)
        p_env = (
            wc
            + wr * (dx_e[..., None] * v1e + dy_e[..., None] * v2e)
            - d_env * wr
        )
        pdf_dir_env = pdf_e
        le_env_s = le_e
    else:
        # unreachable: the compiler builds an envmap for every
        # LIGHT_INFINITE row; keep is_env lanes inert if it ever isn't
        d_env = d_pt
        p_env = jnp.broadcast_to(wc, d_pt.shape)
        pdf_dir_env = jnp.zeros_like(ud1)
        le_env_s = jnp.zeros_like(lL)
    supported = is_pt | is_spot | is_area | is_img | is_distant | is_env

    p = jnp.where(is_area[..., None], p_a, lp)
    p = jnp.where(is_distant[..., None], p_dist, p)
    p = jnp.where(is_env[..., None], p_env, p)
    n = jnp.where(is_area[..., None], n_a, ldir)
    n = jnp.where(is_distant[..., None], -ldir, n)
    n = jnp.where(is_env[..., None], d_env, n)
    d = jnp.where(is_area[..., None], d_a, d_pt)
    d = jnp.where(is_spot[..., None], d_spot, d)
    d = jnp.where(is_distant[..., None], -ldir, d)
    d = jnp.where(is_env[..., None], d_env, d)
    le = jnp.where(is_spot[..., None], le_spot, lL)
    le = jnp.where(is_env[..., None], le_env_s, le)
    if "light_atlas" in dev:
        le_img = lL * _light_map_scale(
            dev, lt, li_idx, d, ltype == LIGHT_GONIO, ltype == LIGHT_PROJECTION, row
        )
        le = jnp.where(is_img[..., None], le_img, le)
    pdf_pos = jnp.where(is_area, pdf_pos_a, 1.0)
    pdf_pos = jnp.where(is_distant | is_env, pdf_pos_dist, pdf_pos)
    pdf_dir = jnp.where(is_area, pdf_dir_a, pdf_dir_pt)
    pdf_dir = jnp.where(is_spot, pdf_dir_spot, pdf_dir)
    pdf_dir = jnp.where(is_distant, 1.0, pdf_dir)
    pdf_dir = jnp.where(is_env, pdf_dir_env, pdf_dir)
    is_delta = is_pt | is_spot | is_img | is_distant
    le = jnp.where(supported[..., None], le, 0.0)
    return LeSample(li_idx, pmf, p, n, d, le, pdf_pos, pdf_dir, is_delta, supported)


def le_pdfs(dev, li_idx, n_emit, w):
    """Light::Pdf_Le for an emission configuration: position pdf (area
    measure) and direction pdf (solid angle) of emitting along w from a
    light-row li_idx whose surface normal is n_emit. Used by BDPT MIS.
    Twosided area lights emit from either face at half the one-sided
    cosine pdf (diffuse.cpp Pdf_Le)."""
    from tpu_pbrt.core.sampling import uniform_cone_pdf

    lt = dev["light"]
    ltype = lt["type"][li_idx]
    cos1 = lt["cos1"][li_idx]
    area = lt["area"][li_idx]
    two = lt["twosided"][li_idx] > 0
    is_pt = ltype == LIGHT_POINT
    is_spot = ltype == LIGHT_SPOT
    is_area = ltype == LIGHT_AREA
    cos_l = dot(n_emit, w)
    pdf_area = jnp.where(
        two, 0.5 * jnp.abs(cos_l) / jnp.pi, jnp.maximum(cos_l, 0.0) / jnp.pi
    )
    pdf_dir = jnp.where(is_pt, 1.0 / (4.0 * jnp.pi), 0.0)
    pdf_dir = jnp.where(is_spot, uniform_cone_pdf(cos1), pdf_dir)
    pdf_dir = jnp.where(is_area, pdf_area, pdf_dir)
    pdf_pos = jnp.where(is_area, 1.0 / jnp.maximum(area, 1e-20), 1.0)
    # distant/infinite (distant.cpp/infinite.cpp Pdf_Le): position over
    # the scene-spanning disk; direction delta (distant) or the env
    # importance pdf (infinite)
    is_distant = ltype == LIGHT_DISTANT
    is_env = ltype == LIGHT_INFINITE
    wr = dev["world_radius"]
    disk_pdf = 1.0 / (jnp.pi * wr * wr)
    pdf_pos = jnp.where(is_distant | is_env, disk_pdf, pdf_pos)
    # distant.cpp Pdf_Le: the direction is a DELTA — pdf 0, which the
    # BDPT MIS ratio walk remaps exactly like other delta junctions
    pdf_dir = jnp.where(is_distant, 0.0, pdf_dir)
    if "envmap" in dev:
        pdf_dir = jnp.where(is_env, env_pdf(dev, -w), pdf_dir)
    return pdf_pos, pdf_dir


def light_pick_pmf(dev, light_distr, li_idx, ref_p=None):
    """Pick pmf of light row li_idx under the integrator's distribution."""
    n = dev["light"]["type"].shape[0]
    light_distr = _bound(dev, light_distr)
    if light_distr is None:
        return jnp.full(jnp.shape(li_idx), 1.0 / n, jnp.float32)
    if isinstance(light_distr, SpatialLightDistribution):
        if ref_p is None:
            return jnp.maximum(light_distr.mean_pmf[jnp.maximum(li_idx, 0)], 1e-12)
        return light_distr.discrete_pdf_at(jnp.maximum(li_idx, 0), ref_p)
    return light_distr.discrete_pdf(jnp.maximum(li_idx, 0))


def emitted_radiance(dev, tri_light, wo_world, n_g):
    """L_e of an intersected emissive triangle (diffuse.cpp
    DiffuseAreaLight::L): emits from the front side unless twosided."""
    lt = dev["light"]
    idx = jnp.maximum(tri_light, 0)
    if "rows" in lt:
        lL, _, two = _emit_columns(lt, idx)
    else:
        lL = small_take(lt["L"], idx)
        two = small_take(lt["twosided"], idx)
    front = dot(n_g, wo_world) > 0.0
    emit = (tri_light >= 0) & (front | (two > 0))
    return jnp.where(emit[..., None], lL, 0.0)
