"""Wide (8-ary) BVH: the TPU-shaped acceleration structure.

Capability match for pbrt-v3 src/accelerators/bvh.cpp BVHAccel::Intersect /
IntersectP — same watertight leaf tests, same closest-hit semantics — but
re-designed for the hardware (SURVEY.md §7 "the hard parts" #1/#2):

- The binary LinearBVHNode walk visits thousands of nodes per ray worst
  case, and a vmapped lockstep while_loop makes EVERY lane pay the worst
  lane's iteration count, with 4-byte scattered gathers each step. On TPU
  that is catastrophic (measured ~30 s per 16k-ray path chunk).
- The wide BVH collapses the binary tree into nodes of up to 8 children.
  One iteration pops a node and slab-tests all 8 child AABBs at once from
  ONE contiguous 48-float row (XLA lowers the row gather to efficient
  vector loads), cutting max iterations by ~4-8x and turning memory traffic
  from scattered scalars into dense rows. Children are pushed far-to-near
  (8-element argsort) so near subtrees pop first, preserving the binary
  version's front-to-back early-out behavior.
- Leaf triangle data is fetched as one contiguous (MAX_LEAF_PRIMS*9)-float
  dynamic slice per leaf pop instead of per-step unrolled gathers.

Build: host-side collapse of the flattened binary BVH (accel/build.py)
by repeatedly expanding the largest-surface-area child until 8 slots fill.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_pbrt.accel.build import MAX_LEAF_PRIMS, BVHArrays
from tpu_pbrt.accel.traverse import Hit, intersect_triangle
from tpu_pbrt.core.vecmath import gamma
from tpu_pbrt.parallel.mesh import vary

WIDTH = 8
# worst-case occupancy is (WIDTH-1)*depth + 1, checked loudly in build_wide;
# 128 covers depth 18 (~8^18 nodes) at 512 B/lane of while_loop state
MAX_STACK = 128
_BOX_EPS = 1.0 + 2.0 * gamma(3)
# wide-leaf encoding in child_idx: >= 0 interior node id;
# < 0 leaf: -(1 + prim_offset * (MAX_LEAF_PRIMS+1) + n_prims)
_LEAF_STRIDE = MAX_LEAF_PRIMS + 1
_EMPTY = np.int32(2**30)  # empty slot: bounds are +inf/-inf, never hit


def slab_test(nmin, nmax, o, inv_d, t_far):
    """Conservative watertight ray/AABB slab test, shared by every walker
    (wide/packet/stream) so the epsilon and NaN semantics cannot diverge.

    nmin/nmax: (..., 3) child bounds; o/inv_d: (..., 3) broadcastable ray;
    t_far: (...) far clip (current closest hit). Returns (t_near, t_far,
    hit) with t_near >= 0 and the 0*inf NaN treated as inside-slab (pbrt's
    conservative ordering: bvh.cpp IntersectP's gamma-widened slabs)."""
    lo = jnp.where(inv_d < 0, nmax, nmin)
    hi = jnp.where(inv_d < 0, nmin, nmax)
    t0 = (lo - o) * inv_d
    t1 = (hi - o) * inv_d * _BOX_EPS
    t0 = jnp.where(jnp.isnan(t0), -jnp.inf, t0)
    t1 = jnp.where(jnp.isnan(t1), jnp.inf, t1)
    tn = jnp.maximum(jnp.max(t0, axis=-1), 0.0)
    tf = jnp.minimum(jnp.min(t1, axis=-1), t_far)
    return tn, tf, tn <= tf


def slab_test_lane_major(b_lo, b_hi, o_c, inv_c):
    """Per-AXIS half of slab_test for lane-major layouts (the stream
    walker's (8, S) arrays): returns this axis's (t0, t1) with the SAME
    _BOX_EPS widening and NaN rules as slab_test above — one source for
    the watertightness semantics, two layouts. Callers combine the three
    axes with explicit min/max chains (no axis reductions) and clamp
    t_near to 0 / t_far to the ray's current hit themselves."""
    lo = jnp.where(inv_c < 0, b_hi, b_lo)
    hi = jnp.where(inv_c < 0, b_lo, b_hi)
    t0 = (lo - o_c) * inv_c
    t1 = (hi - o_c) * inv_c * _BOX_EPS
    t0 = jnp.where(jnp.isnan(t0), -jnp.inf, t0)
    t1 = jnp.where(jnp.isnan(t1), jnp.inf, t1)
    return t0, t1


class WideBVH(NamedTuple):
    child_bmin: jnp.ndarray  # (N, 8, 3)
    child_bmax: jnp.ndarray  # (N, 8, 3)
    child_idx: jnp.ndarray  # (N, 8) encoded


def _area(bmin, bmax):
    d = np.maximum(bmax - bmin, 0)
    return 2 * (d[0] * d[1] + d[0] * d[2] + d[1] * d[2])


def build_wide(bvh: BVHArrays) -> WideBVH:
    """Collapse the flattened binary BVH into 8-wide nodes (host).

    Leaf triangle data is NOT duplicated here: traversal slices the shared
    leaf-order triangle array (`pad_tri_verts` of it) that the scene
    compiler uploads once for both traversal and interaction lookup."""
    n_prims_b = bvh.n_prims
    second = bvh.second_child
    bmin_b = bvh.bounds_min
    bmax_b = bvh.bounds_max
    off_b = bvh.prim_offset

    def leaf_code(b):
        return -(1 + int(off_b[b]) * _LEAF_STRIDE + int(n_prims_b[b]))

    def is_interior(b):
        # the Morton builder pads its complete tree with empty leaves
        # (n_prims == 0, second == 0, inf/-inf bounds); only a forward
        # second-child pointer marks a real interior node
        return n_prims_b[b] == 0 and int(second[b]) > b

    def is_empty_leaf(b):
        return n_prims_b[b] == 0 and int(second[b]) <= b

    wide_nodes = []  # each: list of (binary node id or leaf-code, bmin, bmax)
    # map binary node id -> wide node id (filled as we emit)
    emit_queue = [0]
    wide_id_of: dict = {}

    if n_prims_b[0] > 0:
        # degenerate single-leaf tree
        children = [(leaf_code(0), bmin_b[0], bmax_b[0])]
        wide_nodes.append(children)
    else:
        wide_id_of[0] = 0
        wide_nodes.append(None)  # placeholder
        queue = [0]
        while queue:
            b = queue.pop()
            # expand b's children until 8 slots: keep a worklist of binary
            # subtree roots, split the largest-area interior one each step
            slots = [b + 1, int(second[b])]
            while len(slots) < WIDTH:
                best = -1
                best_a = -1.0
                for i, sb in enumerate(slots):
                    if is_interior(sb):
                        a = _area(bmin_b[sb], bmax_b[sb])
                        if a > best_a:
                            best_a = a
                            best = i
                if best < 0:
                    break
                sb = slots.pop(best)
                slots.append(sb + 1)
                slots.append(int(second[sb]))
            children = []
            for sb in slots:
                if is_empty_leaf(sb):
                    continue  # unhittable padding: no slot at all
                if n_prims_b[sb] > 0:
                    children.append((leaf_code(sb), bmin_b[sb], bmax_b[sb]))
                else:
                    wid = wide_id_of.get(sb)
                    if wid is None:
                        wid = len(wide_nodes)
                        wide_id_of[sb] = wid
                        wide_nodes.append(None)
                        queue.append(sb)
                    children.append((wid, bmin_b[sb], bmax_b[sb]))
            wide_nodes[wide_id_of[b]] = children

    n = len(wide_nodes)
    cmin = np.full((n, WIDTH, 3), np.inf, np.float32)
    cmax = np.full((n, WIDTH, 3), -np.inf, np.float32)
    cidx = np.full((n, WIDTH), _EMPTY, np.int32)
    for i, children in enumerate(wide_nodes):
        for k, (code, bmn, bmx) in enumerate(children):
            cidx[i, k] = code
            cmin[i, k] = bmn
            cmax[i, k] = bmx

    # Loud stack check (replaces a silent top-slot clamp): children always
    # get larger wide ids than their parent, so a reverse pass computes
    # interior depth; each interior pop frees 1 slot and pushes <= WIDTH,
    # giving worst-case occupancy (WIDTH-1)*depth + 1.
    depth = np.ones(n, np.int64)
    for i in range(n - 1, -1, -1):
        for code, _, _ in wide_nodes[i]:
            if code >= 0:
                depth[i] = max(depth[i], 1 + depth[code])
    worst = (WIDTH - 1) * int(depth[0]) + 1
    if worst > MAX_STACK:
        raise ValueError(
            f"wide BVH depth {int(depth[0])} needs stack {worst} > MAX_STACK="
            f"{MAX_STACK}; raise MAX_STACK in accel/wide.py"
        )

    return WideBVH(
        child_bmin=jnp.asarray(cmin),
        child_bmax=jnp.asarray(cmax),
        child_idx=jnp.asarray(cidx),
    )


def pad_tri_verts(tri_verts_leaf_order: np.ndarray) -> np.ndarray:
    """Pad the leaf-order (T,3,3) vertex array with MAX_LEAF_PRIMS zero rows
    so the fixed-size leaf dynamic_slice never reads past the end. The
    padded rows are degenerate triangles (det == 0 -> never hit), so the
    same array safely serves brute-force oracles and interaction gathers."""
    tv = np.ascontiguousarray(tri_verts_leaf_order, dtype=np.float32)
    return np.concatenate([tv, np.zeros((MAX_LEAF_PRIMS, 3, 3), np.float32)], axis=0)


# -------------------------------------------------------------------------
# Device traversal
# -------------------------------------------------------------------------

class _WState(NamedTuple):
    sp: jnp.ndarray
    stack: jnp.ndarray
    t: jnp.ndarray
    prim: jnp.ndarray
    b0: jnp.ndarray
    b1: jnp.ndarray
    iters: jnp.ndarray


_MAX_ITERS = 16384  # safety bound; real traversals finish in hundreds


def _ray_traverse_wide(w: WideBVH, tri_flat, o, d, t_max, any_hit: bool):
    inv_d = 1.0 / d

    def cond(s: _WState):
        return (s.sp > 0) & (s.iters < _MAX_ITERS)

    def body(s: _WState):
        sp = s.sp - 1
        code = s.stack[sp]
        is_leaf = code < 0

        # ---- leaf: contiguous triangle block test -----------------------
        leaf_dec = -(code + 1)
        off = jnp.where(is_leaf, leaf_dec // _LEAF_STRIDE, 0)
        cnt = jnp.where(is_leaf, leaf_dec % _LEAF_STRIDE, 0)
        tri_block = jax.lax.dynamic_slice(
            tri_flat, (off * 9,), (MAX_LEAF_PRIMS * 9,)
        ).reshape(MAX_LEAF_PRIMS, 3, 3)
        h, th, b0h, b1h = intersect_triangle(
            o, d, tri_block[:, 0], tri_block[:, 1], tri_block[:, 2], s.t
        )
        take = is_leaf & (jnp.arange(MAX_LEAF_PRIMS, dtype=jnp.int32) < cnt) & h
        th_m = jnp.where(take, th, jnp.inf)
        k = jnp.argmin(th_m)
        better = th_m[k] < s.t
        t_new = jnp.where(better, th_m[k], s.t)
        prim_new = jnp.where(better, off + k, s.prim)
        b0_new = jnp.where(better, b0h[k], s.b0)
        b1_new = jnp.where(better, b1h[k], s.b1)

        # ---- interior: 8-wide slab test + ordered push ------------------
        node = jnp.where(is_leaf, 0, code)
        nmin = w.child_bmin[node]  # (8,3) one contiguous row
        nmax = w.child_bmax[node]
        cids = w.child_idx[node]
        tn, _, in_slab = slab_test(nmin, nmax, o, inv_d, t_new)
        hit8 = (~is_leaf) & in_slab & (cids != _EMPTY)

        # push far-to-near so near children pop first
        key = jnp.where(hit8, tn, -jnp.inf)
        order = jnp.argsort(key)  # misses (-inf) first, then near..far
        # stack depth is validated loudly at build time (build_wide), so the
        # push needs no runtime clamp
        stack = s.stack
        sp_new = sp
        for j in range(WIDTH - 1, -1, -1):  # far .. near
            c = order[j]
            do = hit8[c]
            stack = jnp.where(do, stack.at[sp_new].set(cids[c]), stack)
            sp_new = jnp.where(do, sp_new + 1, sp_new)

        done_early = jnp.where(any_hit & (prim_new >= 0), jnp.int32(0), sp_new)
        return _WState(done_early, stack, t_new, prim_new, b0_new, b1_new, s.iters + 1)

    init = _WState(
        sp=jnp.int32(1),
        stack=jnp.zeros((MAX_STACK,), jnp.int32),  # stack[0] = root node 0
        t=jnp.asarray(t_max, jnp.float32),
        prim=jnp.int32(-1),
        b0=jnp.float32(0),
        b1=jnp.float32(0),
        iters=jnp.int32(0),
    )
    out = jax.lax.while_loop(cond, body, vary(init))
    return Hit(out.t, out.prim, out.b0, out.b1)


@jax.jit
def wide_intersect(w: WideBVH, tri_verts, o, d, t_max) -> Hit:
    """Closest-hit over a ray batch against the wide BVH. tri_verts is the
    shared padded leaf-order vertex array (see pad_tri_verts)."""
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:-1])
    tri_flat = tri_verts.reshape(-1)
    return jax.vmap(lambda oo, dd, tt: _ray_traverse_wide(w, tri_flat, oo, dd, tt, False))(o, d, t_max)


@jax.jit
def wide_intersect_p(w: WideBVH, tri_verts, o, d, t_max) -> jnp.ndarray:
    """Any-hit (shadow) predicate over a ray batch."""
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:-1])
    tri_flat = tri_verts.reshape(-1)
    hit = jax.vmap(lambda oo, dd, tt: _ray_traverse_wide(w, tri_flat, oo, dd, tt, True))(o, d, t_max)
    return hit.prim >= 0
