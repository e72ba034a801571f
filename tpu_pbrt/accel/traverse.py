"""Device-side ray-scene intersection: watertight triangles + BVH walk.

Capability match for pbrt-v3:
- src/shapes/triangle.cpp Triangle::Intersect/IntersectP — the watertight
  Woop-style shear intersection (translate, permute max-|d| axis to z,
  shear, signed edge functions, scaled depth test).
- src/accelerators/bvh.cpp BVHAccel::Intersect/IntersectP — iterative
  LinearBVHNode traversal with a 64-entry stack, precomputed invDir and
  dir-sign near/far child ordering.

TPU-first design: the single-ray traversal is scalar JAX code vmapped over
the ray batch — under vmap the while_loop runs all lanes in lockstep with
masking, which XLA vectorizes over the VPU. Leaf processing unrolls
MAX_LEAF_PRIMS masked triangle tests. The stream tracer
(accel/stream.py) replaces this on the hot path; this module is the
semantic reference and the testing path.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from tpu_pbrt.core.vecmath import gamma
from tpu_pbrt.parallel.mesh import vary

from tpu_pbrt.accel.build import MAX_LEAF_PRIMS

MAX_STACK = 64
_BOX_EPS = 1.0 + 2.0 * gamma(3)

# Per-dispatch ray-batch cap for the vmapped while_loop walkers: an early
# round saw them fault a v5e somewhere between 2^18 and 2^19 lanes. Not
# re-measured under the installed jax/libtpu; the value stands.
# Integrators must chunk ray batches to at most this many rays per device
# dispatch (they want bounded tile x spp chunks anyway for film
# accumulation and checkpointing).
MAX_RAYS_PER_DISPATCH = 1 << 18


class Hit(NamedTuple):
    """SoA hit record; prim == -1 means miss. b0/b1 are barycentrics of
    vertices 0/1 (b2 = 1-b0-b1). tv optionally carries the hit
    triangle's (…, 3, 3) vertices when the tracer already fetched them —
    per-element gather costs dominate on TPU, so consumers
    (make_interaction) reuse this instead of re-gathering tri_verts."""

    t: jnp.ndarray
    prim: jnp.ndarray
    b0: jnp.ndarray
    b1: jnp.ndarray
    tv: jnp.ndarray | None = None


def intersect_triangle(o, d, p0, p1, p2, t_max):
    """Watertight ray-triangle test; broadcasts over leading axes.

    Returns (hit_mask, t, b0, b1). Follows Triangle::Intersect's shear
    formulation so edge-on rays hit exactly one of two adjacent triangles.
    """
    # translate to ray origin
    p0t = p0 - o
    p1t = p1 - o
    p2t = p2 - o
    # permute so |d| is largest along z; perm derives from d alone, so it
    # must broadcast against each operand's (possibly wider) batch shape —
    # e.g. a single ray (3,) tested against a leaf block (M,3)
    kz = jnp.argmax(jnp.abs(d), axis=-1)
    kx = (kz + 1) % 3
    ky = (kx + 1) % 3
    perm = jnp.stack([kx, ky, kz], axis=-1)

    def permute(a):
        shp = jnp.broadcast_shapes(a.shape, perm.shape)
        return jnp.take_along_axis(
            jnp.broadcast_to(a, shp), jnp.broadcast_to(perm, shp), axis=-1
        )

    dp = permute(d)
    p0t = permute(p0t)
    p1t = permute(p1t)
    p2t = permute(p2t)
    # shear to align ray with +z
    inv_dz = 1.0 / dp[..., 2]
    sx = -dp[..., 0] * inv_dz
    sy = -dp[..., 1] * inv_dz
    x0 = p0t[..., 0] + sx * p0t[..., 2]
    y0 = p0t[..., 1] + sy * p0t[..., 2]
    x1 = p1t[..., 0] + sx * p1t[..., 2]
    y1 = p1t[..., 1] + sy * p1t[..., 2]
    x2 = p2t[..., 0] + sx * p2t[..., 2]
    y2 = p2t[..., 1] + sy * p2t[..., 2]
    # signed edge functions
    e0 = x1 * y2 - y1 * x2
    e1 = x2 * y0 - y2 * x0
    e2 = x0 * y1 - y0 * x1
    det = e0 + e1 + e2
    same_sign = ((e0 >= 0) & (e1 >= 0) & (e2 >= 0)) | ((e0 <= 0) & (e1 <= 0) & (e2 <= 0))
    # scaled depth
    z0 = inv_dz * p0t[..., 2]
    z1 = inv_dz * p1t[..., 2]
    z2 = inv_dz * p2t[..., 2]
    t_scaled = e0 * z0 + e1 * z1 + e2 * z2
    in_range = jnp.where(
        det < 0,
        (t_scaled < 0) & (t_scaled >= t_max * det),
        (t_scaled > 0) & (t_scaled <= t_max * det),
    )
    hit = same_sign & (det != 0) & in_range
    inv_det = 1.0 / jnp.where(det == 0, 1.0, det)
    t = t_scaled * inv_det
    b0 = e0 * inv_det
    b1 = e1 * inv_det
    return hit, t, b0, b1


def brute_force_intersect(tri_verts, o, d, t_max, chunk=4096):
    """Oracle: closest hit over all triangles (SURVEY.md §7 stage 1 oracle).
    o,d: (R,3); tri_verts: (T,3,3). Chunked over T to bound memory."""
    n_tris = tri_verts.shape[0]
    r = o.shape[0]

    def chunk_body(c, state):
        t_best, prim_best, b0_best, b1_best = state
        start = c * chunk
        tv = jax.lax.dynamic_slice(tri_verts, (start, 0, 0), (chunk, 3, 3))
        hit, t, b0, b1 = intersect_triangle(
            o[:, None, :], d[:, None, :], tv[None, :, 0], tv[None, :, 1], tv[None, :, 2], t_best[:, None]
        )
        tri_ids = start + jnp.arange(chunk, dtype=jnp.int32)
        valid = hit & (tri_ids[None, :] < n_tris)
        t = jnp.where(valid, t, jnp.inf)
        k = jnp.argmin(t, axis=1)
        rr = jnp.arange(r, dtype=jnp.int32)
        better = t[rr, k] < t_best
        return (
            jnp.where(better, t[rr, k], t_best),
            jnp.where(better, tri_ids[k], prim_best),
            jnp.where(better, b0[rr, k], b0_best),
            jnp.where(better, b1[rr, k], b1_best),
        )

    n_chunks = (n_tris + chunk - 1) // chunk
    pad = n_chunks * chunk - n_tris
    if pad:
        tri_verts = jnp.concatenate([tri_verts, jnp.zeros((pad, 3, 3), tri_verts.dtype)], axis=0)
    init = (
        jnp.full((r,), t_max, jnp.float32) if jnp.ndim(t_max) == 0 else t_max,
        jnp.full((r,), -1, jnp.int32),
        jnp.zeros((r,), jnp.float32),
        jnp.zeros((r,), jnp.float32),
    )
    t, prim, b0, b1 = jax.lax.fori_loop(0, n_chunks, chunk_body, vary(init))
    return Hit(t, prim, b0, b1)


class _TravState(NamedTuple):
    node: jnp.ndarray
    sp: jnp.ndarray
    stack: jnp.ndarray
    t: jnp.ndarray
    prim: jnp.ndarray
    b0: jnp.ndarray
    b1: jnp.ndarray
    done: jnp.ndarray


def _slab_test(o, inv_d, dir_neg, nmin, nmax, t_cur):
    lo = jnp.where(dir_neg, nmax, nmin)
    hi = jnp.where(dir_neg, nmin, nmax)
    t0 = (lo - o) * inv_d
    t1 = (hi - o) * inv_d * _BOX_EPS
    # 0 * inf (d[axis]==0 with origin exactly on a slab plane) yields NaN;
    # pbrt's comparison ordering treats that conservatively as "inside the
    # slab" — mirror that by mapping NaN to the permissive bound.
    t0 = jnp.where(jnp.isnan(t0), -jnp.inf, t0)
    t1 = jnp.where(jnp.isnan(t1), jnp.inf, t1)
    tn = jnp.maximum(jnp.max(t0), 0.0)
    tf = jnp.minimum(jnp.min(t1), t_cur)
    return tn <= tf


def _ray_traverse(bvh, tri_verts, o, d, t_max, any_hit: bool):
    """Single-ray BVH walk (scalars + fixed stack); vmapped by callers."""
    inv_d = 1.0 / d
    dir_neg = inv_d < 0

    def cond(s: _TravState):
        return ~s.done

    def body(s: _TravState):
        node = s.node
        hit_box = _slab_test(o, inv_d, dir_neg, bvh["bounds_min"][node], bvh["bounds_max"][node], s.t)
        n_prims = bvh["n_prims"][node]
        is_leaf = n_prims > 0
        test_leaf = hit_box & is_leaf

        # unrolled masked leaf tests; clamp the gather index — the final
        # leaf's off+k can run past the triangle array (masked out by
        # k < n_prims, but the gather itself must stay in bounds on TPU)
        t_new, prim_new, b0_new, b1_new = s.t, s.prim, s.b0, s.b1
        off = bvh["prim_offset"][node]
        n_tris = tri_verts.shape[0]
        for k in range(MAX_LEAF_PRIMS):
            pidx = jnp.minimum(off + k, n_tris - 1)
            tri = tri_verts[pidx]
            h, th, b0h, b1h = intersect_triangle(o, d, tri[0], tri[1], tri[2], t_new)
            take = test_leaf & (k < n_prims) & h
            t_new = jnp.where(take, th, t_new)
            prim_new = jnp.where(take, pidx, prim_new)
            b0_new = jnp.where(take, b0h, b0_new)
            b1_new = jnp.where(take, b1h, b1_new)

        # descend interior front-to-back, else pop
        go_down = hit_box & ~is_leaf
        ax = bvh["axis"][node]
        neg = dir_neg[ax]
        first = jnp.where(neg, bvh["second_child"][node], node + 1)
        second = jnp.where(neg, node + 1, bvh["second_child"][node])
        stack = jnp.where(go_down, s.stack.at[s.sp].set(second), s.stack)
        sp_push = jnp.where(go_down, s.sp + 1, s.sp)
        # pop path
        exhausted = sp_push == 0
        sp_pop = jnp.maximum(sp_push - 1, 0)
        popped = stack[sp_pop]
        next_node = jnp.where(go_down, first, popped)
        next_sp = jnp.where(go_down, sp_push, sp_pop)
        done = jnp.where(go_down, False, exhausted)
        if any_hit:
            done = done | (prim_new >= 0)
        return _TravState(next_node, next_sp, stack, t_new, prim_new, b0_new, b1_new, done)

    init = _TravState(
        node=jnp.int32(0),
        sp=jnp.int32(0),
        stack=jnp.zeros((MAX_STACK,), jnp.int32),
        t=jnp.asarray(t_max, jnp.float32),
        prim=jnp.int32(-1),
        b0=jnp.float32(0),
        b1=jnp.float32(0),
        done=jnp.bool_(False),
    )
    out = jax.lax.while_loop(cond, body, vary(init))
    return Hit(out.t, out.prim, out.b0, out.b1)


@partial(jax.jit, static_argnames=())
def bvh_intersect(bvh, tri_verts, o, d, t_max) -> Hit:
    """Closest-hit for a ray batch. bvh: dict of SoA arrays; o,d: (R,3);
    t_max: scalar or (R,)."""
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:-1])
    return jax.vmap(lambda oo, dd, tt: _ray_traverse(bvh, tri_verts, oo, dd, tt, False))(o, d, t_max)


@partial(jax.jit, static_argnames=())
def bvh_intersect_p(bvh, tri_verts, o, d, t_max) -> jnp.ndarray:
    """Any-hit (shadow ray) predicate for a ray batch -> bool (R,)."""
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:-1])
    hit = jax.vmap(lambda oo, dd, tt: _ray_traverse(bvh, tri_verts, oo, dd, tt, True))(o, d, t_max)
    return hit.prim >= 0


def bvh_as_device_dict(bvh_arrays) -> dict:
    """BVHArrays (numpy) -> device dict consumed by the traversal kernels.
    Fails loudly if the tree is deeper than the fixed traversal stack."""
    import numpy as _np

    n_prims = _np.asarray(bvh_arrays.n_prims)
    second = _np.asarray(bvh_arrays.second_child)
    n = n_prims.shape[0]
    depth = _np.ones(n, _np.int64)
    # DFS layout: children have larger ids. Interior nodes are n_prims == 0
    # with a forward second-child pointer; the Morton build also emits empty
    # padded leaves (n_prims == 0, second == 0, inf/-inf bounds) which the
    # traversal never descends — skip them here the same way.
    for i in range(n - 1, -1, -1):
        if n_prims[i] == 0 and second[i] > i and i + 1 < n:
            depth[i] = 1 + max(depth[i + 1], depth[second[i]])
    if int(depth[0]) > MAX_STACK:
        raise ValueError(
            f"binary BVH depth {int(depth[0])} exceeds MAX_STACK={MAX_STACK}; "
            "raise MAX_STACK in accel/traverse.py"
        )
    return {
        "bounds_min": jnp.asarray(bvh_arrays.bounds_min, jnp.float32),
        "bounds_max": jnp.asarray(bvh_arrays.bounds_max, jnp.float32),
        "prim_offset": jnp.asarray(bvh_arrays.prim_offset, jnp.int32),
        "n_prims": jnp.asarray(bvh_arrays.n_prims, jnp.int32),
        "second_child": jnp.asarray(bvh_arrays.second_child, jnp.int32),
        "axis": jnp.asarray(bvh_arrays.axis, jnp.int32),
    }
