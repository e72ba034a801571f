"""Triangle intersection as matrix multiply — the MXU leaf test.

Capability match for pbrt-v3 src/shapes/triangle.cpp Triangle::Intersect
(same hit set and barycentrics up to f32 rounding), re-derived for the
TPU's systolic array. The key observation: every quantity the
Möller–Trumbore test needs is a BILINEAR form in (ray, triangle). With
e1 = v1-v0, e2 = v2-v0, s = o-v0, p = d x e2, q = s x e1:

    det   = p . e1 = d . (e2 x e1)                    (linear in d)
    u*det = p . s  = sum_ij o_i d_j [eps_ijk e2_k] - d . (e2 x v0)
    v*det = q . d  = sum_ij o_i d_j [-eps_ijk e1_k] - d . (v0 x e1)
    t*det = q . e2 = o . n - v0 . n,   n = e1 x e2    (linear in o)

so with the 16-dim ray feature vector

    phi(o, d) = [o_i d_j (9, i-major), d (3), o (3), 1]

all four outputs for T triangles are one matmul phi @ W with per-triangle
weights W in R^{16 x 4T} — exactly the (rays, 16) @ (16, 4T) shape the MXU
wants. Intersecting a 64-triangle treelet against a 128-ray packet costs
one small matmul instead of 64 gathered scalar tests.

f32 precision: the o_i d_j features lose ~eps*|o||d| per term, so rays and
vertices are RE-CENTERED per treelet (o' = o - c, v0' = v0 - c), bounding
the cancellation by the treelet diameter instead of the scene diameter.
The matmul asks for Precision.HIGHEST, and on the v5e gets it: six bf16
passes, 22.4 bits of the product against float64 (`tools/edge_probe.py
product`, PERF.md Findings PR 27; the CPU reads 22.5). HIGH is three
passes and reads 13.9 bits, the DEFAULT one pass and 7.5: bf16 features
would visibly crack edges, and a product that does not name its precision
gets exactly that on the TPU. Edge behavior: unlike the shear-based
watertight test (accel/traverse.py intersect_triangle, which this module
does NOT replace for oracle/unit-test use), the barycentric comparisons
here use a small epsilon band, so shared-edge rays may hit BOTH adjacent
triangles (closest-t wins — harmless) but never leak through.

The brute path (scenes of at most BRUTE_MAX_TRIS triangles: no hierarchy)
does NOT take the feature product: `brute_intersect` tests every (ray,
triangle) pair element-wise, one triangle a loop step with the rays on the
lanes. With a few dozen columns the product filled a sliver of the MXU and
its output had to be decoded element-wise anyway: the loop is 5.4 times
quicker at 36 triangles and twice at 256 (v5e, 2^19 rays).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_pbrt.accel.traverse import Hit
from tpu_pbrt.parallel.mesh import vary

#: relative barycentric tolerance: widens each triangle by ~1e-6 so shared
#: edges cannot crack open under f32 rounding (double hits resolve by t)
EDGE_EPS = 1e-6

#: scenes at or below this triangle count skip the treelet hierarchy and
#: test every ray against every triangle (Cornell-class scenes)
BRUTE_MAX_TRIS = 256


def tri_feature_weights_raw(verts: np.ndarray, center) -> np.ndarray:
    """(T,3,3) triangle vertices + re-centering point(s) -> (T, 16, 4)
    per-triangle weights (outputs: det, u*det, v*det, t*det).

    `center` broadcasts against (T,3,3) — pass (3,) for a shared center or
    (T,1,3) for per-triangle centers. Degenerate (zero-area) triangles —
    including padding rows — produce all-zero weights, so det == 0 and
    they can never hit.
    """
    v = np.asarray(verts, np.float64) - np.asarray(center, np.float64)
    v0, v1, v2 = v[:, 0], v[:, 1], v[:, 2]
    e1 = v1 - v0
    e2 = v2 - v0
    n = np.cross(e1, e2)  # (T,3)
    T = len(v)

    eps = np.zeros((3, 3, 3))
    eps[0, 1, 2] = eps[1, 2, 0] = eps[2, 0, 1] = 1.0
    eps[0, 2, 1] = eps[2, 1, 0] = eps[1, 0, 2] = -1.0

    W = np.zeros((T, 16, 4), np.float64)
    # det = d . (e2 x e1) = -d . n
    W[:, 9:12, 0] = -n
    # u*det = sum o'_i d_j eps_ijk e2_k  -  d . (e2 x v0')
    W[:, :9, 1] = np.einsum("ijk,tk->tij", eps, e2).reshape(T, 9)
    W[:, 9:12, 1] = -np.cross(e2, v0)
    # v*det = sum o'_i d_j (-eps_ijk e1_k)  -  d . (v0' x e1)
    W[:, :9, 2] = -np.einsum("ijk,tk->tij", eps, e1).reshape(T, 9)
    W[:, 9:12, 2] = -np.cross(v0, e1)
    # t*det = o' . n - v0' . n
    W[:, 12:15, 3] = n
    W[:, 15, 3] = -np.sum(v0 * n, axis=-1)
    return W.astype(np.float32)


def tri_feature_weights_motion(v0: np.ndarray, v1: np.ndarray,
                               center) -> np.ndarray:
    """Motion-blur feature weights: vertices lerp linearly over the
    shutter, so every Moller-Trumbore output is a CUBIC in the ray time
    t (det and u/v*det are quadratic, t_hit*det cubic via v0(t).n(t)).
    The per-triangle weights become 4 monomial coefficient blocks
    W(t) = W_0 + t W_1 + t^2 W_2 + t^3 W_3, fit EXACTLY by evaluating
    the static weights at 4 nodes and applying the inverse Vandermonde
    (float64). The matmul consumes the extended 64-dim ray feature
    phi(o, d) (x) [1, t, t^2, t^3], which accel/stream.py builds.
    -> (T, 64, 4)."""
    nodes = np.array([0.0, 1.0 / 3.0, 2.0 / 3.0, 1.0])
    vand_inv = np.linalg.inv(np.vander(nodes, 4, increasing=True))  # (4,4)
    ws = []
    for t in nodes:
        vt = (1.0 - t) * np.asarray(v0, np.float64) + t * np.asarray(v1, np.float64)
        ws.append(tri_feature_weights_raw(vt, center).astype(np.float64))
    wstack = np.stack(ws, axis=0)  # (4, T, 16, 4) values at nodes
    coeffs = np.einsum("kn,ntfo->ktfo", vand_inv, wstack)  # (4, T, 16, 4)
    # rows: [W0(16) | W1(16) | W2(16) | W3(16)] -> (T, 64, 4)
    return np.concatenate([coeffs[k] for k in range(4)], axis=1).astype(np.float32)


def ray_features(o_c, d):
    """Re-centered origins (...,3) + directions (...,3) -> phi (...,16)."""
    od = o_c[..., :, None] * d[..., None, :]  # (...,3,3) i-major
    one = jnp.ones(o_c.shape[:-1] + (1,), o_c.dtype)
    return jnp.concatenate(
        [od.reshape(od.shape[:-2] + (9,)), d, o_c, one], axis=-1
    )


def decode_outputs(out, n_tris: int, t_max):
    """Matmul output (..., 4T) -> per-ray closest hit over the T columns.

    Returns (t, k, b0, b1) where k is the LOCAL triangle index in [0, T)
    (or arbitrary when t == +inf => miss) and b0/b1 follow the Hit
    convention (b0 = 1-u-v weight of v0, b1 = u weight of v1).
    """
    T = n_tris
    det = out[..., 0 * T : 1 * T]
    udet = out[..., 1 * T : 2 * T]
    vdet = out[..., 2 * T : 3 * T]
    tdet = out[..., 3 * T : 4 * T]
    inv = 1.0 / jnp.where(det == 0.0, 1.0, det)
    u = udet * inv
    v = vdet * inv
    t = tdet * inv
    tm = t_max[..., None] if jnp.ndim(t_max) else t_max
    hit = (
        (det != 0.0)
        & (u >= -EDGE_EPS)
        & (v >= -EDGE_EPS)
        & (u + v <= 1.0 + EDGE_EPS)
        & (t > 0.0)
        & (t < tm)
    )
    t = jnp.where(hit, t, jnp.inf)
    k = jnp.argmin(t, axis=-1)
    t_best = jnp.take_along_axis(t, k[..., None], axis=-1)[..., 0]
    u_best = jnp.take_along_axis(u, k[..., None], axis=-1)[..., 0]
    v_best = jnp.take_along_axis(v, k[..., None], axis=-1)[..., 0]
    b0 = 1.0 - u_best - v_best
    b1 = u_best
    return t_best, k, b0, b1


class BruteWork(NamedTuple):
    """One wave's work on the brute path: what `obs/counters.py` sums into
    `brute_rays` (the pairs are the rays times the table's static T)."""

    rays: jnp.ndarray  # i32 live rays (t_max > 0): each met every triangle


def tri_edge_table(verts: np.ndarray) -> np.ndarray:
    """(T,3,3) triangle vertices -> the brute test's (T, 9) float32 table,
    a row a triangle: v0.xyz, e1.xyz, e2.xyz, the edges differenced in
    float64 and rounded once."""
    v = np.asarray(verts, np.float64)
    return np.concatenate(
        [v[:, 0], v[:, 1] - v[:, 0], v[:, 2] - v[:, 0]], axis=1
    ).astype(np.float32)


def brute_tris(dev) -> int:
    """Static triangle count of a scene's brute table; 0 where the scene
    compiler chose another acceleration structure."""
    return int(dev["brute"]["tab"].shape[0]) if "brute" in dev else 0


def brute_intersect(tab, o, d, t_max, time=None, tab1=None) -> Hit:
    """Closest hit of rays (R,3) against ALL T triangles of `tab`
    (tri_edge_table): the small-scene acceleration path, no hierarchy.

    One loop over the triangles; each step is a plain element-wise
    float32 Moeller-Trumbore test of ONE triangle (nine scalars) against
    all R rays, the rays on the lanes, and a running closest hit. No
    matrix product: at T <= 256 a `(rays, 16) @ (16, 4T)` feature product
    fills a sliver of the MXU at six bf16 passes and its `(rays, 4T)`
    output has to be decoded element-wise anyway (v5e, 2^19 rays: 3.6 ms
    here against 19.5 ms at T=36, 19.2 against 38.1 at T=256; PERF.md,
    Findings PR 27). s = o - v0 is formed per pair, so cancellation is
    bounded by the ray-to-triangle distance and nothing is re-centered.
    Same EDGE_EPS band as decode_outputs, and its tiebreak: the lowest
    index wins a tie. Motion blur: `tab1` is the shutter-close table and
    `time` the per-ray shutter time in [0,1] (None: shutter open);
    vertices lerp, so v0, e1 and e2 lerp with them."""
    R = o.shape[0]
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), (R,))
    moving = tab1 is not None and time is not None
    if moving:
        tm = jnp.broadcast_to(jnp.asarray(time, jnp.float32), (R,))
    ox, oy, oz = o[:, 0], o[:, 1], o[:, 2]
    dx, dy, dz = d[:, 0], d[:, 1], d[:, 2]

    def one_triangle(k, best):
        t_best, k_best, u_best, v_best = best
        row = tab[k]
        if moving:
            row = row[:, None] + tm * (tab1[k] - row)[:, None]  # (9, R)
        ax, ay, az, bx, by, bz, cx, cy, cz = (row[i] for i in range(9))
        px, py, pz = dy * cz - dz * cy, dz * cx - dx * cz, dx * cy - dy * cx
        det = bx * px + by * py + bz * pz
        inv = 1.0 / jnp.where(det == 0.0, 1.0, det)
        sx, sy, sz = ox - ax, oy - ay, oz - az
        u = (sx * px + sy * py + sz * pz) * inv
        qx, qy, qz = sy * bz - sz * by, sz * bx - sx * bz, sx * by - sy * bx
        v = (dx * qx + dy * qy + dz * qz) * inv
        t = (cx * qx + cy * qy + cz * qz) * inv
        closer = (
            (det != 0.0)
            & (u >= -EDGE_EPS)
            & (v >= -EDGE_EPS)
            & (u + v <= 1.0 + EDGE_EPS)
            & (t > 0.0)
            & (t < t_max)
            & (t < t_best)
        )
        return (
            jnp.where(closer, t, t_best),
            jnp.where(closer, k, k_best),
            jnp.where(closer, u, u_best),
            jnp.where(closer, v, v_best),
        )

    zero = jnp.zeros((R,), jnp.float32)
    t, prim, u, v = jax.lax.fori_loop(
        0, tab.shape[0], one_triangle,
        vary((jnp.full((R,), jnp.inf, jnp.float32),
              jnp.full((R,), -1, jnp.int32), zero, zero)),
    )
    return Hit(t, prim, 1.0 - u - v, u)
