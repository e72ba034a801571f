"""Two-level acceleration structure: treelets + top-level wide BVH (host build).

Capability match for pbrt-v3 src/accelerators/bvh.cpp BVHAccel (same hit
semantics), re-shaped for the TPU memory system. The reference's
LinearBVHNode[] walk gathers one 32-byte node per ray per step — on TPU
that per-lane gather pattern is row-latency-bound and catastrophically
slow (measured ~0.05us PER ROW regardless of row size). The TPU-shaped
layout instead:

- cuts the binary SAH/Morton tree (accel/build.py) into TREELETS —
  subtrees of <= LEAF_TRIS triangles, contiguous in leaf order — and
  precomputes each treelet's 16 x 4L Möller–Trumbore feature matrix
  (accel/mxu.py), so a leaf visit is one fat contiguous row fetch + one
  MXU matmul instead of L scattered scalar tests;
- builds a small top-level BVH over treelet AABBs and collapses it 8-wide
  (accel/wide.py build_wide), so interior traversal touches ~100x fewer
  nodes than the triangle-level tree;
- is traversed per PACKET (accel/packet.py): 128 rays share one traversal
  stack, so node fetches are per-packet rows, not per-ray rows.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from tpu_pbrt.accel.build import BVHArrays, build_bvh
from tpu_pbrt.accel.mxu import tri_feature_weights_motion, tri_feature_weights_raw
from tpu_pbrt.accel.wide import _LEAF_STRIDE, WideBVH, build_wide

#: triangles per treelet (feature-matrix columns = 4x this). 64 keeps the
#: treelet feature row at 16 KB — one efficient contiguous fetch.
LEAF_TRIS = 64
#: treelets whose feature weights build_treelet_pack holds at once
_PACK_SLAB = 512


class TreeletPack(NamedTuple):
    """Device arrays for the two-level traversal (all jnp — every field is
    a pytree leaf so the pack passes through jit; static metadata like
    leaf_tris is derived from shapes: feat.shape == (C, 4*leaf_tris, 16)).

    The feature layout is TRANSPOSED relative to accel/mxu.py's standalone
    (16, 4T) weights: rows are output columns, so a leaf block feeds the
    MXU as dot(featT (4L,16), phiT (16,128)) with the 128 rays on the lane
    dimension — the contraction the stream flush's einsum runs
    (accel/stream.py::_flush). Only this one layout is stored: it is
    the scene's largest array (~0.5 GB for crown-class), so keeping a
    second transposed copy for the packet walker would double device
    residency; the packet walker transposes per-leaf instead."""

    top: WideBVH  # 8-wide top tree; leaf codes encode treelet ids
    featT: jnp.ndarray  # (C, 16, 4*LEAF_TRIS) f32 MT feature matrices
    center: jnp.ndarray  # (C, 3) f32 re-centering point per treelet
    offset: jnp.ndarray  # (C,) i32 first leaf-order triangle id
    count: jnp.ndarray  # (C,) i32 triangles in treelet

    @property
    def leaf_tris(self) -> int:
        return self.featT.shape[2] // 4

    @property
    def n_features(self) -> int:
        """16 static, 64 with motion-blur time features."""
        return self.featT.shape[1]

    @property
    def n_treelets(self) -> int:
        return self.featT.shape[0]


def _subtree_ranges(bvh: BVHArrays):
    """Per-node (first leaf-order prim, prim count) via a reverse DFS pass.

    DFS layout: children of interior node i are i+1 and second_child[i],
    both with larger ids, so a reverse iteration sees children first.
    Morton padding leaves (n_prims == 0, no forward second-child) count 0.
    """
    n = bvh.n_nodes
    second = bvh.second_child
    n_prims = bvh.n_prims
    count = np.zeros(n, np.int64)
    first = np.zeros(n, np.int64)
    for i in range(n - 1, -1, -1):
        if n_prims[i] > 0:
            count[i] = n_prims[i]
            first[i] = bvh.prim_offset[i]
        elif second[i] > i:
            count[i] = count[i + 1] + count[second[i]]
            first[i] = first[i + 1]
    return first, count


def cut_treelets(bvh: BVHArrays, leaf_tris: int = LEAF_TRIS):
    """Top-down cut of the binary tree into subtrees of <= leaf_tris prims.

    Returns (offsets, counts, bmin, bmax) numpy arrays, one row per
    treelet. Subtree prims are contiguous in leaf order, so a treelet is
    just a range [offset, offset+count) of the leaf-order triangle array.
    """
    first, count = _subtree_ranges(bvh)
    offsets, counts, bmins, bmaxs = [], [], [], []
    stack = [0]
    while stack:
        i = stack.pop()
        if count[i] == 0:
            continue  # Morton padding
        if count[i] <= leaf_tris:
            offsets.append(first[i])
            counts.append(count[i])
            bmins.append(bvh.bounds_min[i])
            bmaxs.append(bvh.bounds_max[i])
        else:
            stack.append(int(bvh.second_child[i]))
            stack.append(i + 1)
    return (
        np.asarray(offsets, np.int64),
        np.asarray(counts, np.int64),
        np.asarray(bmins, np.float32),
        np.asarray(bmaxs, np.float32),
    )


def decode_top_leaf(code):
    """Top-tree wide leaf code -> treelet id (inverse of build_wide's
    leaf encoding with one 'primitive' — a treelet — per leaf)."""
    return (-(code + 1)) // _LEAF_STRIDE


def build_treelet_pack(
    tri_verts_leaf_order: np.ndarray, bvh: BVHArrays,
    leaf_tris: int = LEAF_TRIS, tri_verts1: np.ndarray = None,
) -> TreeletPack:
    """Cut + features + top tree. tri_verts_leaf_order: (T,3,3) float32 in
    the SAME leaf order the BVH's prim_offset indexes (the scene compiler's
    permuted triangle array, unpadded). tri_verts1 (same order): the
    shutter-end keyframe — features become the 64-row cubic-in-time
    tables of accel/mxu.py tri_feature_weights_motion, and the caller's
    bvh must be built over union bounds."""
    off, cnt, bmin, bmax = cut_treelets(bvh, leaf_tris)
    c = len(off)

    # top tree over treelet AABBs, one treelet per leaf; its prim_order
    # permutes treelets, so reorder the treelet arrays to match
    top_bin = build_bvh(bmin, bmax, method="sah" if c <= 262144 else "hlbvh",
                        max_leaf_prims=1)
    order = top_bin.prim_order
    off, cnt = off[order], cnt[order]
    top = build_wide(top_bin)

    # Vectorized padded gather of every treelet's triangles + per-treelet
    # feature build (crown-class scenes have ~10k treelets; a Python loop
    # over treelets would dominate scene compile on a single host core),
    # a SLAB of treelets at a time: the float64 weights of a slab and
    # their transposes are the only intermediates, and the one array of
    # the pack's size is the float32 table that goes to the device (whole,
    # the same intermediates are ~10 GB of host memory at 12,000 treelets)
    verts = np.asarray(tri_verts_leaf_order, np.float32)
    verts1 = None if tri_verts1 is None else np.asarray(tri_verts1, np.float32)
    t_total = len(verts)
    n_feat = 16 if verts1 is None else 64
    featT = np.empty((c, n_feat, 4 * leaf_tris), np.float32)
    center = np.empty((c, 3), np.float32)
    lane = np.arange(leaf_tris)[None, :]
    for lo in range(0, c, _PACK_SLAB):
        sl = slice(lo, min(lo + _PACK_SLAB, c))
        n = sl.stop - sl.start
        gidx = np.clip(off[sl, None] + lane, 0, t_total - 1)  # (n, L)
        valid = lane < cnt[sl, None]
        tv = verts[gidx]  # (n, L, 3, 3)
        tv[~valid] = 0.0  # zero pad: det == 0, never hits
        both, ok = tv, valid
        if verts1 is not None:
            tv1 = verts1[gidx]
            tv1[~valid] = 0.0
            both = np.concatenate([tv, tv1], axis=1)
            ok = np.tile(valid, (1, 2))
        vmin = np.where(ok[..., None], both.min(axis=2), np.inf).min(axis=1)
        vmax = np.where(ok[..., None], both.max(axis=2), -np.inf).max(axis=1)
        ctr = (0.5 * (vmin + vmax)).astype(np.float32)  # (n, 3)
        center[sl] = ctr
        per_tri = np.repeat(ctr, leaf_tris, axis=0)[:, None, :]
        if verts1 is not None:
            W = tri_feature_weights_motion(
                tv.reshape(n * leaf_tris, 3, 3),
                tv1.reshape(n * leaf_tris, 3, 3), per_tri,
            )
        else:
            W = tri_feature_weights_raw(tv.reshape(n * leaf_tris, 3, 3), per_tri)
        # (n, L, F, 4) -> (n, F, 4, L) -> (n, F, 4L): columns grouped
        # [det(L) | u*det(L) | v*det(L) | t*det(L)], matching
        # decode_outputs' column order after the (c,f,b) x (c,f,k)
        # contraction of the stream flush
        featT[sl] = W.reshape(n, leaf_tris, n_feat, 4).transpose(0, 2, 3, 1).reshape(
            n, n_feat, 4 * leaf_tris
        )

    return TreeletPack(
        top=top,
        featT=jnp.asarray(featT),
        center=jnp.asarray(center),
        offset=jnp.asarray(off, jnp.int32),
        count=jnp.asarray(cnt, jnp.int32),
    )
