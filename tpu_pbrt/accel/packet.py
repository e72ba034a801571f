"""Packet traversal of the two-level treelet BVH — the fast trace path.

Capability match for pbrt-v3 src/accelerators/bvh.cpp
BVHAccel::Intersect/IntersectP (same closest-hit/any-hit semantics over
the same tree), re-architected for TPU memory behavior. Why not the
reference's per-ray stack walk: on TPU a gather costs ~constant time PER
ROW (latency-bound), so R rays each fetching one node row per step costs
R rows * steps — measured 5 orders of magnitude off target in round 2.

The packet design divides the R-ray batch into packets of LANE=128 rays
that share ONE traversal stack (classic CPU-SIMD packet tracing, mapped
to the VPU lane dimension):

- node fetches are per-PACKET rows (R/128 of them per step, not R);
- all per-lane work is dense (P, 128, 8) vector math — no per-lane
  gathers, no per-lane stacks, no argsort;
- a popped top-level node expands 8 children at once (slab tests against
  every lane); children hit by ANY lane are pushed with their packet-min
  entry distance, and a pop whose entry distance exceeds the packet-max
  current hit t is discarded (front-to-back culling at packet grain);
- treelet leaves are queued per packet, sorted by entry distance, and
  intersected with one MXU feature matmul per (packet, treelet) pair
  (accel/mxu.py) — 64 watertight-equivalent triangle tests per lane in
  one contiguous 16 KB row fetch + (128,16)@(16,256) matmul;
- the leaf queue is bounded: when it fills mid-walk the traversal flushes
  (tests queued treelets, tightening per-lane t), then resumes — so
  arbitrarily divergent packets stay correct with fixed memory.

Coherence determines the packet-union overhead: camera rays from adjacent
pixels traverse near-identical node sets; integrators keep bounce rays in
their parent packets (spatial coherence) — see integrators/common.py.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from tpu_pbrt.accel.mxu import decode_outputs, ray_features
from tpu_pbrt.accel.traverse import Hit
from tpu_pbrt.accel.treelet import TreeletPack
from tpu_pbrt.accel.wide import _EMPTY, MAX_STACK
from tpu_pbrt.parallel.mesh import vary

LANE = 128
LEAF_QUEUE = 64
_FLUSH_AT = LEAF_QUEUE - 8  # a pop can append up to 8 leaves


class _State(NamedTuple):
    sp: jnp.ndarray  # (P,) stack depth
    stk_c: jnp.ndarray  # (P,S) i32 interior node codes
    stk_t: jnp.ndarray  # (P,S) f32 packet-min entry distance
    nleaf: jnp.ndarray  # (P,) queued leaf count
    leaf_id: jnp.ndarray  # (P,Q) i32 treelet ids
    leaf_tn: jnp.ndarray  # (P,Q) f32 entry distances
    t: jnp.ndarray  # (P,LANE) current closest hit (or t_max)
    prim: jnp.ndarray  # (P,LANE) i32 global leaf-order triangle id, -1 miss
    b0: jnp.ndarray  # (P,LANE)
    b1: jnp.ndarray  # (P,LANE)
    n_pop: jnp.ndarray  # (P,) stat: interior pops (BVHAccel nodes-visited)
    n_tl: jnp.ndarray  # (P,) stat: treelet (leaf matmul) tests


def _packet_done(s: _State, dead, any_hit: bool):
    if not any_hit:
        return jnp.zeros(s.sp.shape, bool)
    return jnp.all((s.prim >= 0) | dead, axis=-1)


def _traverse(tp: TreeletPack, o, d, t_max, any_hit: bool):
    """o,d: (P,LANE,3); t_max: (P,LANE). Returns final _State."""
    P = o.shape[0]
    L = tp.leaf_tris
    inv_d = 1.0 / d
    dead = t_max <= 0.0
    p_idx = jnp.arange(P, dtype=jnp.int32)

    top = tp.top
    from tpu_pbrt.accel.treelet import decode_top_leaf

    def interior_step(s: _State):
        active = (s.sp > 0) & (s.nleaf <= _FLUSH_AT) & ~_packet_done(s, dead, any_hit)
        sp1 = jnp.maximum(s.sp - 1, 0)
        code = s.stk_c[p_idx, sp1]
        tn_top = s.stk_t[p_idx, sp1]
        sp_new = jnp.where(active, sp1, s.sp)
        t_pkt = jnp.max(s.t, axis=-1)  # packet-max current hit distance
        expand = active & (tn_top <= t_pkt)

        node = jnp.where(expand, code, 0)
        nmin = top.child_bmin[node]  # (P,8,3)
        nmax = top.child_bmax[node]
        cids = top.child_idx[node]  # (P,8)

        # slab test: every lane vs all 8 children, far plane clamped by the
        # lane's current t (adaptive front-to-back culling)
        from tpu_pbrt.accel.wide import slab_test

        tn, _, lane_hit = slab_test(
            nmin[:, None], nmax[:, None], o[:, :, None, :],
            inv_d[:, :, None, :], s.t[:, :, None],
        )  # (P,LANE,8)
        hit8 = jnp.any(lane_hit, axis=1) & (cids != _EMPTY) & expand[:, None]
        tn_pkt = jnp.min(jnp.where(lane_hit, tn, jnp.inf), axis=1)  # (P,8)

        is_int = hit8 & (cids >= 0)
        is_leaf = hit8 & (cids < 0)

        # push interior children (one scatter; unpushed slots -> OOB drop)
        npush = jnp.cumsum(is_int, axis=-1)
        pos = jnp.where(is_int, sp_new[:, None] + npush - 1, MAX_STACK + 7)
        stk_c = s.stk_c.at[p_idx[:, None], pos].set(cids, mode="drop")
        stk_t = s.stk_t.at[p_idx[:, None], pos].set(tn_pkt, mode="drop")
        sp_out = sp_new + npush[:, -1]

        # queue leaf children (treelet ids)
        tids = decode_top_leaf(cids)
        nq = jnp.cumsum(is_leaf, axis=-1)
        qpos = jnp.where(is_leaf, s.nleaf[:, None] + nq - 1, LEAF_QUEUE + 7)
        leaf_id = s.leaf_id.at[p_idx[:, None], qpos].set(tids, mode="drop")
        leaf_tn = s.leaf_tn.at[p_idx[:, None], qpos].set(tn_pkt, mode="drop")
        nleaf = s.nleaf + nq[:, -1]

        return s._replace(
            sp=sp_out, stk_c=stk_c, stk_t=stk_t,
            nleaf=nleaf, leaf_id=leaf_id, leaf_tn=leaf_tn,
            n_pop=s.n_pop + active.astype(jnp.int32),
        )

    def leaf_step(c):
        k, s = c
        valid = (k < s.nleaf) & ~_packet_done(s, dead, any_hit)
        t_pkt = jnp.max(s.t, axis=-1)
        tid = jnp.where(valid, s.leaf_id[:, k], 0)
        # queue is tn-sorted: once the packet's next treelet is farther
        # than its farthest lane hit, every later one is too
        live = valid & (s.leaf_tn[:, k] <= t_pkt) & (tid >= 0)

        WT = tp.featT[jnp.where(live, tid, 0)]  # (P,16,4L)
        ctr = tp.center[jnp.where(live, tid, 0)]  # (P,3)
        off = tp.offset[jnp.where(live, tid, 0)]  # (P,)
        phi = ray_features(o - ctr[:, None, :], d)  # (P,LANE,16)
        out = jnp.einsum(
            "plf,pfc->plc", phi, WT, precision=jax.lax.Precision.HIGHEST
        )
        t_new, k_loc, b0, b1 = decode_outputs(out, L, s.t)
        better = live[:, None] & jnp.isfinite(t_new) & (t_new < s.t)
        return k + 1, s._replace(
            t=jnp.where(better, t_new, s.t),
            prim=jnp.where(better, off[:, None] + k_loc.astype(jnp.int32), s.prim),
            b0=jnp.where(better, b0, s.b0),
            b1=jnp.where(better, b1, s.b1),
            n_tl=s.n_tl + live.astype(jnp.int32),
        )

    def flush(s: _State):
        """Sort the leaf queue by entry distance, intersect front-to-back."""
        key = jnp.where(
            jnp.arange(LEAF_QUEUE, dtype=jnp.int32)[None, :] < s.nleaf[:, None],
            s.leaf_tn, jnp.inf
        )
        key_s, id_s = jax.lax.sort([key, s.leaf_id], num_keys=1)
        s = s._replace(leaf_tn=key_s, leaf_id=id_s)

        def cond(c):
            k, ss = c
            t_pkt = jnp.max(ss.t, axis=-1)
            live = (
                (k < ss.nleaf)
                & (ss.leaf_tn[:, jnp.minimum(k, LEAF_QUEUE - 1)] <= t_pkt)
                & ~_packet_done(ss, dead, any_hit)
            )
            return (k < LEAF_QUEUE) & jnp.any(live)

        _, s = jax.lax.while_loop(cond, leaf_step, vary((jnp.int32(0), s)))
        return s._replace(nleaf=jnp.zeros_like(s.nleaf))

    def outer_cond(s: _State):
        alive = ((s.sp > 0) | (s.nleaf > 0)) & ~_packet_done(s, dead, any_hit)
        return jnp.any(alive)

    def outer_body(s: _State):
        def a_cond(ss: _State):
            active = (
                (ss.sp > 0) & (ss.nleaf <= _FLUSH_AT)
                & ~_packet_done(ss, dead, any_hit)
            )
            return jnp.any(active)

        s = jax.lax.while_loop(a_cond, interior_step, s)
        return flush(s)

    init = _State(
        sp=jnp.ones((P,), jnp.int32),
        stk_c=jnp.zeros((P, MAX_STACK), jnp.int32),  # stack[0] = root
        stk_t=jnp.zeros((P, MAX_STACK), jnp.float32),
        nleaf=jnp.zeros((P,), jnp.int32),
        leaf_id=jnp.full((P, LEAF_QUEUE), -1, jnp.int32),
        leaf_tn=jnp.full((P, LEAF_QUEUE), jnp.inf, jnp.float32),
        t=t_max,
        prim=jnp.full((P, LANE), -1, jnp.int32),
        b0=jnp.zeros((P, LANE), jnp.float32),
        b1=jnp.zeros((P, LANE), jnp.float32),
        n_pop=jnp.zeros((P,), jnp.int32),
        n_tl=jnp.zeros((P,), jnp.int32),
    )
    return jax.lax.while_loop(outer_cond, outer_body, vary(init))


@partial(jax.jit, static_argnames=("any_hit",))
def packet_traverse_stats(tp: TreeletPack, o, d, t_max, any_hit: bool = False):
    """Per-packet traversal statistics (interior pops, treelet matmul
    tests) for the stats subsystem and perf analysis."""
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:-1])
    op, dp, tm, _ = _to_packets(o, d, t_max)
    s = _traverse(tp, op, dp, tm, any_hit)
    return s.n_pop, s.n_tl


def _to_packets(o, d, t_max):
    R = o.shape[0]
    P = (R + LANE - 1) // LANE
    pad = P * LANE - R
    if pad:
        o = jnp.concatenate([o, jnp.zeros((pad, 3), o.dtype)])
        d = jnp.concatenate([d, jnp.full((pad, 3), 1.0, d.dtype)])
        t_max = jnp.concatenate([t_max, jnp.full((pad,), -1.0, t_max.dtype)])
    return (
        o.reshape(P, LANE, 3),
        d.reshape(P, LANE, 3),
        t_max.reshape(P, LANE),
        R,
    )


@partial(jax.jit, static_argnames=("any_hit",))
def packet_intersect(tp: TreeletPack, o, d, t_max, any_hit: bool = False):
    """Closest hit (or any-hit predicate source) for a flat ray batch.

    o,d: (R,3); t_max scalar or (R,). Returns Hit with global leaf-order
    triangle ids, API-compatible with bvh_intersect/wide_intersect.
    """
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:-1])
    op, dp, tp_, R = _to_packets(o, d, t_max)
    s = _traverse(tp, op, dp, tp_, any_hit)
    flat = lambda a: a.reshape(-1)[:R]  # noqa: E731
    t = flat(s.t)
    prim = flat(s.prim)
    t = jnp.where(prim >= 0, t, jnp.inf)
    return Hit(t, prim, flat(s.b0), flat(s.b1))


def packet_intersect_p(tp: TreeletPack, o, d, t_max):
    """Any-hit (shadow) predicate -> bool (R,)."""
    hit = packet_intersect(tp, o, d, t_max, any_hit=True)
    return hit.prim >= 0
