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
from tpu_pbrt.accel.mxu import tri_feature_weights_raw
from tpu_pbrt.accel.wide import _LEAF_STRIDE, WideBVH, build_wide

#: triangles per treelet (feature-matrix columns = 4x this). 64 keeps the
#: treelet feature row at 16 KB — one efficient contiguous fetch.
LEAF_TRIS = 64


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
    # feature build (crown-class scenes have ~50k treelets; a Python loop
    # here would dominate scene compile on a single host core).
    verts = np.asarray(tri_verts_leaf_order, np.float32)
    t_total = len(verts)
    gidx = off[:, None] + np.arange(leaf_tris)[None, :]  # (C, L)
    valid = np.arange(leaf_tris)[None, :] < cnt[:, None]
    tv = verts[np.clip(gidx, 0, t_total - 1)]  # (C, L, 3, 3)
    tv[~valid] = 0.0  # zero pad: det == 0, never hits
    if tri_verts1 is not None:
        tv1 = np.asarray(tri_verts1, np.float32)[np.clip(gidx, 0, t_total - 1)]
        tv1[~valid] = 0.0
        both = np.concatenate([tv, tv1], axis=1)
        vmin = np.where(
            np.tile(valid, (1, 2))[..., None], both.min(axis=2), np.inf
        ).min(axis=1)
        vmax = np.where(
            np.tile(valid, (1, 2))[..., None], both.max(axis=2), -np.inf
        ).max(axis=1)
    else:
        vmin = np.where(valid[..., None], tv.min(axis=2), np.inf).min(axis=1)
        vmax = np.where(valid[..., None], tv.max(axis=2), -np.inf).max(axis=1)
    center = (0.5 * (vmin + vmax)).astype(np.float32)  # (C, 3)
    if tri_verts1 is not None:
        from tpu_pbrt.accel.mxu import tri_feature_weights_motion

        W = tri_feature_weights_motion(
            tv.reshape(c * leaf_tris, 3, 3),
            tv1.reshape(c * leaf_tris, 3, 3),
            np.repeat(center, leaf_tris, axis=0)[:, None, :],
        ).reshape(c, leaf_tris, 64, 4)
        feat = np.ascontiguousarray(
            W.transpose(0, 3, 1, 2).reshape(c, 4 * leaf_tris, 64)
        )
    else:
        W = tri_feature_weights_raw(
            tv.reshape(c * leaf_tris, 3, 3),
            np.repeat(center, leaf_tris, axis=0)[:, None, :],
        ).reshape(c, leaf_tris, 16, 4)
        # (C, L, 16, 4) -> (C, 4, L, 16) -> (C, 4L, 16): rows grouped
        # [det(L) | u*det(L) | v*det(L) | t*det(L)], matching
        # decode_outputs' column order after the (...,f) x (k,f) contraction
        feat = np.ascontiguousarray(
            W.transpose(0, 3, 1, 2).reshape(c, 4 * leaf_tris, 16)
        )

    return TreeletPack(
        top=top,
        featT=jnp.asarray(np.ascontiguousarray(feat.transpose(0, 2, 1))),
        center=jnp.asarray(center),
        offset=jnp.asarray(off, jnp.int32),
        count=jnp.asarray(cnt, jnp.int32),
    )
