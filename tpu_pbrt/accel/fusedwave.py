"""Pallas TPU wavefront kernels: the fused flush + expand stages.

Stage one of the bounce megakernel (ROADMAP direction #1 / PAPER.md's
"one fused Pallas wavefront kernel"). The stream tracer's two dense
phases each become ONE Pallas grid:

FLUSH (`fused_flush_chunk`): the whole leaf-intersection pipeline for a
chunk of 128-ray treelet blocks — per-block ray-feature gather +
re-center (the phi build), the treelet feature row DMA'd HBM->VMEM by a
scalar-prefetch index_map (the schedule the retired TPU_PBRT_PREFETCH
kernel pioneered), the Möller–Trumbore MXU product, the per-lane
closest-hit decode, AND the cross-block per-ray merge against
VMEM-resident (R,) winner accumulators. The jnp path materializes the
(CH, 16, 128) phi tensor, a (CH, 16, 4L) gathered feature copy and the
(CH, 128, 4L) matmul product in HBM and re-reads them through decode and
`_merge_chunk`'s sort; the kernel's only HBM traffic is the feature rows
(once per block), the (CH, 128) block tables, the (8, R) ray table
(fetched once per chunk) and the final (R,) t/prim winners.

EXPAND (`fused_expand`): the dense middle of the traversal step — the
per-pair ray fetch, the 8-child node fetch (the one-hot MXU matmul for
small top trees, exactly `stream._fetch_children`'s table so culling
stays bit-identical, or the native take for big ones), the lane-major
slab tests and the packed push-key build — with the popped stack slab
resident in VMEM for the whole grid. The sort-based compaction stays at
jnp level: lax.sort has no Pallas lowering and XLA's int-key radix path
is already the measured-fast primitive (accel/stream.py module doc).

Bit-identity contract (pinned by tests/test_fusedwave.py in interpret
mode): identical EDGE_EPS band, identical argmin tiebreak (lowest local
triangle index), and a merge whose final (t, prim) equals the jnp
`_merge_chunk` sort exactly. Two structural arguments make the simpler
in-kernel forms safe:

- the kernel drops the per-block `t < t_max` pre-cull: removing the
  upper bound only ADDS candidates with t >= the ray's current best,
  and the merge's strict `<` rejects every one of them, so the final
  winner (and its tie-break) cannot change;
- the sequential strict-`<` merge in grid order equals the chunked
  stable-sort merge: lax.sort is stable, so among equal-(ray, t)
  candidates the jnp path keeps buffer order — exactly the grid order —
  and `<` keeps the first winner, `.at[].min` + strict-`<` prim update
  keep it too.

TPU grid steps execute sequentially, which is what makes the
accumulator outputs (constant index_map -> block revisiting keeps them
in VMEM across the whole grid) and the ordered merge sound. Both
pallas_calls DECLARE that requirement (`dimension_semantics =
("arbitrary",)` below): a dim flipped to "parallel" would let megacore
interleave grid steps across cores and silently race the accumulator
merge — pallascheck's PC-RACE rule fails the repo gate on exactly that
flip, and PC-INIT pins the `@pl.when(b == 0)` accumulator seed.
Interpret mode (`interpret=True` on CPU backends) preserves the same
sequential semantics — that is the CPU testing story.

VMEM budgets are no longer hand-derived here: the per-grid-step
footprint of every kernel (double-buffered moving blocks + resident
accumulators + flat scratch) is computed statically by
`tpu_pbrt/analysis/pallascheck.py`, gated against the committed
`analysis/vmem_budgets.json`, and INVERTED to derive the maximal safe
caps — `python -m tpu_pbrt.analysis.pallascheck --derive-caps` prints
the maximal TPU_PBRT_FUSED_MAX_RAYS / MAX_NODES per platform VMEM
size; the config.py defaults (2^18 rays, 2^14 nodes) are a checked
consequence of that model (PC-CAPS), not folklore.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_pbrt.accel.mxu import EDGE_EPS
from tpu_pbrt.accel.treelet import decode_top_leaf
from tpu_pbrt.accel.wide import _EMPTY, slab_test_lane_major

#: rays per leaf block (the MXU matmul row dim — mirrors stream.BLOCK)
BLOCK = 128
#: lanes per fused-expand grid step
EXPAND_TILE = 1024

_I32_MAX = np.int32(2**31 - 1)

#: Mosaic dimension semantics for the two 1-D grids. "arbitrary" =
#: sequential execution in grid order — the property BOTH correctness
#: proofs above rest on (the ordered closest-hit merge and the b == 0
#: accumulator seed). Declared explicitly (not left to the Mosaic
#: default) so pallascheck's PC-RACE rule verifies it per kernel;
#: flipping either to ("parallel",) fails `python -m tpu_pbrt.analysis`.
FLUSH_DIM_SEMANTICS = ("arbitrary",)
EXPAND_DIM_SEMANTICS = ("arbitrary",)


# --------------------------------------------------------------------------
# FLUSH: phi build + treelet DMA + MT matmul + decode + closest-hit merge
# --------------------------------------------------------------------------


def _seed_accumulators(t_in_ref, p_in_ref, t_out_ref, p_out_ref):
    """Seed the VMEM-resident winner accumulators from the wave's
    current (t, prim) — must run on grid step 0, before any merge reads
    them (pallascheck PC-INIT fails the repo gate if this goes missing);
    they are written back to HBM only once, after the last grid step."""
    t_out_ref[...] = t_in_ref[...]
    p_out_ref[...] = p_in_ref[...]


def _flush_kernel(meta_ref, feat_ref, rid_ref, rayF_ref, t_in_ref,
                  p_in_ref, t_out_ref, p_out_ref, t_scr, p_scr,
                  *, L: int, motion: bool):
    """One grid step = one leaf block (one treelet x 128 rays).

    meta row (8,) i32: [treelet id, prim offset, center xyz (f32 bits),
    block live flag, 0, 0]. The treelet id drove the scalar-prefetch
    index_map that DMA'd feat_ref before this body ran."""
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        _seed_accumulators(t_in_ref, p_in_ref, t_out_ref, p_out_ref)

    @pl.when(meta_ref[b, 5] > 0)
    def _():
        rid = rid_ref[0]  # (128,) i32, -1 = empty slot
        ridc = jnp.maximum(rid, 0)
        # the block-build gather: 128 ray columns (o, d, t, time) pulled
        # from the VMEM-resident lane-major ray table — the jnp path's
        # (8, CH*BLOCK) HBM gather + (CH, 8, BLOCK) swap, fused away
        rr = jnp.take(rayF_ref[...], ridc, axis=1)  # (8, 128)
        ctr = jnp.stack([
            jax.lax.bitcast_convert_type(meta_ref[b, 2 + i], jnp.float32)
            for i in range(3)
        ])  # (3,) treelet re-center point
        oc = [rr[i] - ctr[i] for i in range(3)]
        dc = [rr[3 + i] for i in range(3)]
        phiT = jnp.stack(
            [oc[i] * dc[j] for i in range(3) for j in range(3)]
            + dc + oc + [jnp.ones_like(oc[0])],
        )  # (16, 128) — same row order as stream._flush's jnp build
        if motion:
            tm_r = rr[7]
            phiT = jnp.concatenate(
                [phiT, phiT * tm_r[None, :],
                 phiT * (tm_r * tm_r)[None, :],
                 phiT * (tm_r * tm_r * tm_r)[None, :]],
                axis=0,
            )  # (64, 128) cubic-in-time features
        featT = feat_ref[0]  # (F, 4L), F features on the contraction dim
        out4 = jax.lax.dot_general(
            featT, phiT,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # (4L, 128)
        det = out4[0 * L: 1 * L]
        udet = out4[1 * L: 2 * L]
        vdet = out4[2 * L: 3 * L]
        tdet = out4[3 * L: 4 * L]
        inv = 1.0 / jnp.where(det == 0.0, 1.0, det)
        u = udet * inv
        v = vdet * inv
        t = tdet * inv
        # same EDGE_EPS band as mxu.decode_outputs; the t < t_max bound
        # is enforced by the merge's strict `<` below (see module doc)
        hit = (
            (det != 0.0)
            & (u >= -EDGE_EPS)
            & (v >= -EDGE_EPS)
            & (u + v <= 1.0 + EDGE_EPS)
            & (t > 0.0)
        )
        tm = jnp.where(hit, t, jnp.inf)  # (L, 128)
        # argmin = the lowest local index among equal-t hits — the
        # pinned tiebreak, identical to decode_outputs
        t_scr[...] = jnp.min(tm, axis=0, keepdims=True)
        k = jnp.argmin(tm, axis=0, keepdims=True).astype(jnp.int32)
        p_scr[...] = meta_ref[b, 1] + k  # global leaf-order prim id

        def lane(i, carry):
            r = rid_ref[0, i]
            # clamp BOTH ends: ray ids are < R by construction (and the
            # store is r >= 0 guarded), so the clip is value-identical —
            # it exists so pallascheck's PC-OOB interval proof closes on
            # the meta-driven accumulator indexing below
            rc = jnp.clip(r, 0, t_out_ref.shape[1] - 1)
            tc = t_scr[0, i]
            cur = t_out_ref[0, rc]

            @pl.when((r >= 0) & (tc < cur))
            def _():
                # Pallas REF stores (mutable by contract), reached via
                # fori_loop so the AST walk cannot see the pallas_call
                # boundary above them
                t_out_ref[0, rc] = tc  # jaxlint: disable=JL-MUT
                p_out_ref[0, rc] = p_scr[0, i]  # jaxlint: disable=JL-MUT

            return carry

        # sequential per-lane scatter-min: ray ids within a block are
        # unique (a ray reaches a treelet leaf at most once per wave),
        # so lane order inside the loop is immaterial; grid order
        # supplies the buffer order the stable-sort merge would use
        jax.lax.fori_loop(0, BLOCK, lane, 0)


@partial(jax.jit, static_argnames=("interpret",))
def fused_flush_chunk(feat_table, meta, rid_rows, rayF, t_row, prim,
                      interpret: bool = False):
    """Fold one chunk of leaf blocks into the per-ray best (t, prim).

    feat_table: (C, F, 4L) full treelet feature table, resident in HBM —
    the grid's scalar-prefetch index_map DMAs exactly row meta[b, 0] per
    step. meta: (CH, 8) i32 per-block scalars (see _flush_kernel).
    rid_rows: (CH, 128) i32 ray ids, -1 = empty slot. rayF: (8, R)
    lane-major ray table [o | d | t | time]. t_row/prim: (R,) current
    winners. Returns the updated (t_row, prim) — the ONLY per-chunk HBM
    writes."""
    CH = meta.shape[0]
    _, F, fourL = feat_table.shape
    L = fourL // 4
    R = rayF.shape[1]
    t2 = t_row[None, :]
    p2 = prim[None, :]
    # under the tile shard_map the outputs vary over the mesh as the
    # ray table does (jax requires the kernel to say so)
    vma = jax.typeof(rayF).vma
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(CH,),
        in_specs=[
            pl.BlockSpec((1, F, fourL), lambda i, m: (m[i, 0], 0, 0)),
            pl.BlockSpec((1, BLOCK), lambda i, m: (i, 0)),
            pl.BlockSpec((8, R), lambda i, m: (0, 0)),
            pl.BlockSpec((1, R), lambda i, m: (0, 0)),
            pl.BlockSpec((1, R), lambda i, m: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, R), lambda i, m: (0, 0)),
            pl.BlockSpec((1, R), lambda i, m: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, BLOCK), jnp.float32),
            pltpu.VMEM((1, BLOCK), jnp.int32),
        ],
    )
    t_out, p_out = pl.pallas_call(
        partial(_flush_kernel, L=L, motion=(F == 64)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, R), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((1, R), jnp.int32, vma=vma),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=FLUSH_DIM_SEMANTICS,
        ),
        # a stable name: pallascheck keys its budgets on it, and a
        # profiler trace finds the kernel by it
        name="_flush_kernel",
        interpret=interpret,
    )(meta, feat_table, rid_rows, rayF, t2, p2)
    return t_out[0], p_out[0]


# --------------------------------------------------------------------------
# EXPAND: ray fetch + child fetch + slab tests + push-key build
# --------------------------------------------------------------------------


def _expand_kernel(key_ref, node_ref, rayE_ref, *refs,
                   tb: int, R: int, use_onehot: bool, any_hit: bool):
    """One grid step = EXPAND_TILE popped (ray, node) pairs: everything
    stream._expand does between the stack pop and the compaction sort.
    refs order: [prim (any_hit)] + ([tab64] if use_onehot else
    [box48, cid]) + [key_out, cand_out, live_out]."""
    refs = list(refs)
    prim_ref = refs.pop(0) if any_hit else None
    if use_onehot:
        tab_ref = refs.pop(0)
    else:
        box_ref = refs.pop(0)
        cid_ref = refs.pop(0)
    key_out_ref, cand_out_ref, live_out_ref = refs

    key_in = key_ref[0]  # (T,) i32; invalid/pad lanes carry I32_MAX
    node = node_ref[0]  # (T,) i32
    T = key_in.shape[0]
    rid = jnp.clip((key_in - (1 << 30)) >> tb, 0, R - 1)
    if tb:
        comp = (key_in - (1 << 30)) & ((1 << tb) - 1)
        tn_in = jax.lax.bitcast_convert_type(
            ((1 << tb) - 1 - comp) << (31 - tb), jnp.float32
        )
    else:
        tn_in = jnp.zeros_like(key_in, jnp.float32)
    tn_in = jnp.where(key_in != _I32_MAX, tn_in, jnp.inf)
    rows = jnp.take(rayE_ref[...], rid, axis=1)  # (8, T)
    t_r = rows[6]
    live = (key_in != _I32_MAX) & (tn_in <= t_r)
    if any_hit:
        live = live & (jnp.take(prim_ref[0], rid) < 0)

    if use_onehot:
        # the SAME clamped 64-row table + rounding reassembly as
        # stream._fetch_children: culling decisions (1-ulp box wobble
        # absorbed by _BOX_EPS) stay bit-identical to the jnp path
        tab64 = tab_ref[...]  # (64, N)
        N = tab64.shape[1]
        oh = (
            node[None, :] == jax.lax.broadcasted_iota(jnp.int32, (N, T), 0)
        ).astype(jnp.float32)
        out = jax.lax.dot(
            tab64, oh, precision=jax.lax.Precision.HIGHEST
        )  # (64, T)
        nb = out[:48].reshape(6, 8, T)
        lo = jnp.round(out[48:56]).astype(jnp.int32)
        hi = jnp.round(out[56:64]).astype(jnp.int32)
        cids = (hi << 16) | lo
    else:
        nb = jnp.take(box_ref[...], node, axis=1).reshape(6, 8, T)
        cids = jnp.take(cid_ref[...], node, axis=1)  # (8, T)

    ray6 = rows[0:6]
    tx0, tx1 = slab_test_lane_major(nb[0], nb[3], ray6[0][None, :], ray6[3][None, :])
    ty0, ty1 = slab_test_lane_major(nb[1], nb[4], ray6[1][None, :], ray6[4][None, :])
    tz0, tz1 = slab_test_lane_major(nb[2], nb[5], ray6[2][None, :], ray6[5][None, :])
    tn8 = jnp.maximum(jnp.maximum(tx0, ty0), jnp.maximum(tz0, 0.0))
    tf8 = jnp.minimum(jnp.minimum(tx1, ty1), jnp.minimum(tz1, t_r[None, :]))
    in_slab = tn8 <= tf8

    hit8 = live[None, :] & in_slab & (cids != _EMPTY)
    is_int = hit8 & (cids >= 0)
    is_leaf = hit8 & (cids < 0)
    rid8 = jnp.broadcast_to(rid[None, :], cids.shape)
    if tb:
        qtn = jax.lax.shift_right_logical(
            jax.lax.bitcast_convert_type(tn8, jnp.int32), 31 - tb
        )
    else:
        qtn = 0
    key_leaf = rid8
    key_int = (1 << 30) + (rid8 << tb) + (((1 << tb) - 1) - qtn)
    key_out_ref[...] = jnp.where(
        is_leaf, key_leaf, jnp.where(is_int, key_int, _I32_MAX)
    )
    cand_out_ref[...] = jnp.where(is_leaf, decode_top_leaf(cids), cids)
    live_out_ref[...] = live.astype(jnp.int32)[None, :]


@partial(jax.jit, static_argnames=("tb", "use_onehot", "any_hit", "interpret"))
def fused_expand(key_in, node, rayE, prim, tab64, box48, cid,
                 tb: int, use_onehot: bool, any_hit: bool,
                 interpret: bool = False):
    """Child candidates for a popped stack slab, in one Pallas grid.

    key_in/node: (S,) packed interior keys + node ids (invalid lanes
    already masked to I32_MAX / 0 by the caller — they produce dead
    output keys). rayE: (8, R) lane-major [o | inv_d | t]. prim: (R,)
    current hit ids (read only under any_hit; pass anything otherwise).
    tab64 OR box48+cid: the node table in the SAME representation the
    jnp `_fetch_children` would use for this top tree. Returns
    (key8, cand8, live) of shapes ((8, Sp), (8, Sp), (Sp,)) where
    Sp >= S is S rounded up to the grid tile; the pad lanes are dead
    (key = I32_MAX) and the caller's compaction sort drops them."""
    S = key_in.shape[0]
    R = rayE.shape[1]
    vma = jax.typeof(rayE).vma  # see fused_flush_chunk
    tile = min(EXPAND_TILE, S)
    n_tiles = -(-S // tile)
    sp = n_tiles * tile
    if sp != S:
        key_in = jnp.concatenate(
            [key_in, jnp.full((sp - S,), _I32_MAX, jnp.int32)]
        )
        node = jnp.concatenate([node, jnp.zeros((sp - S,), jnp.int32)])

    in_specs = [
        pl.BlockSpec((1, tile), lambda i: (0, i)),
        pl.BlockSpec((1, tile), lambda i: (0, i)),
        pl.BlockSpec((8, R), lambda i: (0, 0)),
    ]
    args = [key_in[None, :], node[None, :], rayE]
    if any_hit:
        in_specs.append(pl.BlockSpec((1, R), lambda i: (0, 0)))
        args.append(prim[None, :])
    if use_onehot:
        N = tab64.shape[1]
        in_specs.append(pl.BlockSpec((64, N), lambda i: (0, 0)))
        args.append(tab64)
    else:
        N = box48.shape[1]
        in_specs.append(pl.BlockSpec((48, N), lambda i: (0, 0)))
        in_specs.append(pl.BlockSpec((8, N), lambda i: (0, 0)))
        args.extend([box48, cid])

    key8, cand8, live = pl.pallas_call(
        partial(_expand_kernel, tb=tb, R=R, use_onehot=use_onehot,
                any_hit=any_hit),
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((8, tile), lambda i: (0, i)),
            pl.BlockSpec((8, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((8, sp), jnp.int32, vma=vma),
            jax.ShapeDtypeStruct((8, sp), jnp.int32, vma=vma),
            jax.ShapeDtypeStruct((1, sp), jnp.int32, vma=vma),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=EXPAND_DIM_SEMANTICS,
        ),
        name="_expand_kernel",
        interpret=interpret,
    )(*args)
    return key8, cand8, live[0]
