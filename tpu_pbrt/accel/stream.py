"""Stream (sort/compaction wavefront) BVH traversal — the fast trace path.

Capability match for pbrt-v3 src/accelerators/bvh.cpp
BVHAccel::Intersect/IntersectP (same closest-hit/any-hit semantics over the
same SAH tree), re-architected a third time for TPU execution behavior.

Why not the packet walk (accel/packet.py): packets amortize node fetches
only while the 128 rays in a packet agree on a traversal path. Bounce rays
(cosine-sampled hemispheres) disagree almost immediately, the packet's
union frustum covers the whole scene, and every lane pays for every node
any lane wants — measured 4 orders of magnitude slower than coherent
camera rays on the same kernel.

Why not a per-ray stack walk (accel/wide.py): a vmapped while_loop makes
every ray pay the worst ray's iteration count, and each iteration moves a
few hundred bytes per ray — far below the row sizes TPU memory wants.

The stream design has NO per-ray control flow at all. Traversal state is
one flat LIFO worklist of (ray, node, t_entry) pairs shared by the whole
wave, processed in large dense slabs. Primitive costs measured on a v5e
in an early round (not re-measured under the installed jax/libtpu)
dictate the shape of every step:

- jax.lax.sort hits a FAST radix-like path only for INT32 keys with at
  most 3 operand arrays (~1 ms / 1M elements); a float key or a 4th
  array falls back to a comparator sort (~7 ms / 1M). Every sort in this
  file therefore uses a single packed-i32 key and <= 3 arrays.
- gathers cost ~13-21 ns per INDEX whatever a row holds, and ORDER
  does not help: one take of 131,072 rows from an (8, 2^19) table reads
  12.9 ns an index at random, 16.5 sorted (unique or in runs) and 20.8
  on an iota (tools/expand_probe.py on this v5e: PERF.md, PR 36; the
  early round's "nearly-sorted indices approach ~1 ns" is not what
  this stack does); scatters are worst of all. Gathers from SMALL
  tables are instead computed on the MXU as a
  one-hot matmul (~0.4 ms for 131k lookups of a 48-float row vs ~8 ms
  for the native gather).

EXPAND pops a slab of SLAB pairs at once (one contiguous dynamic_slice),
culls pairs whose recorded entry distance already exceeds their ray's
current hit, slab-tests each pair's ray against its node's 8 child boxes
in one dense (8, SLAB) lane-major test. The node's 8 child boxes AND the
8 child codes (as two exact 16-bit halves) ride ONE one-hot matmul:
(64, N) static table @ (N, S) one-hot at Precision.HIGHEST — exact for
the integer rows, and within 1 ulp for the box rows, absorbed by the
slab test's _BOX_EPS widening. Most of the 8*SLAB tested children are
misses (a pair has 1.4-1.5 hit children: PERF.md, PR 36) and a sort is
paid by the key, so each pair's hit children are first packed down the
8-row axis into _PACK_ROWS candidate rows (_pack_children: a prefix
count and selects on full lanes). A pair with more hit children than
rows emits one fewer and, in the last row, ITSELF, with the key it was
popped with and the child index it stopped at in its code's top bits:
it lands on the stack on top of its own children and its next pop takes
up from that child. The _PACK_ROWS*SLAB candidates are then compacted
with ONE 2-array int-key sort whose packed key is

    leaf:     ray                                  (sorts first)
    interior: 2^30 + (ray << TN_BITS) + ~quant(t_entry)
    dead:     INT32_MAX

so leaves compact to the front (appended to the leaf buffer with one
contiguous write), interiors land grouped BY RAY with each ray's nearest
children pushed on top of the LIFO stack (per-ray front-to-back order —
stronger culling than any global distance order, because only a ray's
OWN near leaves can tighten its t). (The ray-major order buys the
per-ray take of o/inv_d/t nothing: a gather is paid by the index, and
grouped or sorted indices read no cheaper than random ones.) The entry
distance lives ONLY in the key's low quantized bits: the pop-side cull
rebuilds a conservative underestimate from them (mantissa tail
zero-filled), so dropping the exact f32 plane costs a fraction of a
percent of extra pairs but removes a third sort array and a whole
stack plane.

FLUSH runs when the leaf buffer is nearly full (or the stack empties):
it sorts the buffered (ray, treelet) pairs by a packed (treelet << RAY_
BITS | ray) key, so each treelet's rays form one contiguous, ray-sorted
run (past 4,096 treelets the pair [treelet, ray] is sorted on the
treelet alone: _flush_key_packed). Every run is cut into blocks counted
from ITS OWN start (_cut_blocks: rank within the run, one running
maximum a flush), so a run of n pairs costs ceil(n / height) blocks and
no block is cut by a position that belongs to the buffer, not to the
run. The block's height is static, chosen from the pairs a flush waits
for over the treelets that share them (_flush_block: 128 rays while a
treelet's run fills them, lower where thousands of treelets share a
wave). The chunk loop runs the blocks in trips whose ray slots are
static too (_flush_trip: an eighth of a slab, so that a threshold flush
is many trips and its last trip's empty tail a small part of it, and
4,096 at most, where a slot is cheapest), whatever the height. The
trips carry the rays' closest hits as one (R,) row; the flush writes it
into the ray tables once, at its end. Block starts are recovered with
a second single-array int sort (position-of-k-th-set-bit via sort —
searchsorted is ~100x slower on TPU), and each block of rays is intersected against its treelet's
triangles in one MXU feature matmul (accel/mxu.py): (height, 16) ray
features x (16, 4L) per-treelet Moller-Trumbore weights. Closest hits
merge per chunk by sorting the chunk's candidates on a packed
(ray, t-bits) key pair and scattering only each ray-run's HEAD (its
argmin): two small mostly-dropped scatters at sorted unique indices
replace the per-slot scatter-min + equality-select pair that dominated
the round-3 profile.

Sequential depth per wave is ~(total pairs / SLAB) big dense steps, and
leaf work lands on the MXU in (height, 16) @ (16, 4L) tiles regardless
of ray order. Ray coherence changes only the pair COUNT, never the
execution shape. Dead lanes (t_max <= 0) are sorted out of the initial
stack, so bounce/shadow waves cost ~(live rays), not R.

The acceleration structure is the same two-level TreeletPack as the
packet walk (accel/treelet.py) with fatter leaves (STREAM_LEAF_TRIS):
the MXU makes triangle tests nearly free, so trading deeper trees for
fatter matmuls moves work from the latency-bound worklist to the
compute units.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_pbrt.accel.mxu import decode_outputs
from tpu_pbrt.accel.traverse import Hit
from tpu_pbrt.config import cfg
from tpu_pbrt.accel.treelet import TreeletPack, decode_top_leaf
from tpu_pbrt.accel.wide import _EMPTY, slab_test_lane_major
from tpu_pbrt.obs import phases as ph
from tpu_pbrt.parallel.mesh import vary

#: triangles per treelet for the stream path (feature row = 4*this
#: columns). An early-round sweep on a v5e ranked 512 over 256 (more
#: worklist pairs) and over 1024 (the matmul cost finally dominates);
#: not re-measured under the installed jax/libtpu, the value stands
#: (tools/sweep_leaf.py re-runs the sweep).
STREAM_LEAF_TRIS = 512
#: rays per leaf block at most — the MXU matmul's row dimension; the
#: height a scene takes is _flush_block's answer
BLOCK = 128
#: the lowest block _flush_block answers
_MIN_BLOCK = 32
#: the most and the fewest ray slots a trip of the flush's chunk loop
#: runs, whatever the block's height; the trip a wave takes is
#: _flush_trip's answer. Swept on a v5e at both wave widths the cells
#: run (PERF.md, PR 34): a slot costs the flush 0.070 us in trips of
#: 32,768, 0.064 at 16,384 and 0.049 at 8,192 and 4,096 (a trip's
#: product is slots * 4L floats: 32 MB at 4,096), and a trip 10-18 us
#: for being one, the more the wider the wave. At 8,192 killeroo's
#: one-chip frame is 0.9 % shorter and crown-geometry's, whose trip of
#: 32-ray blocks fetches four times the treelet rows, 6.8 % longer
_MAX_TRIP = 32 * BLOCK
_MIN_TRIP = 16 * BLOCK
#: safety bound on while_loop iterations (real waves take tens to hundreds)
_MAX_ITERS = 1 << 16
#: above this top-node count the one-hot box matmul's N dimension costs
#: more than the native gather it replaces — and its materialized (N, S)
#: one-hot operand (N * 131072 * 4 bytes per EXPAND) starts to threaten
#: HBM. 512 is the largest measured-good size (~268 MB operand).
_ONEHOT_MAX_NODES = 512
#: the widest wave a chip traces by default: the pool's fused camera +
#: shadow wave of a 2^20-ray dispatch, 2 x 262,144 lanes
FUSED_WAVE_RAYS = 1 << 19
#: candidate rows a pair keeps of its 8 tested children for EXPAND's
#: sort (_pack_children): the sort is paid by the key (1.58 ms for 2^20
#: keys, 0.70 for 2^19, 0.43 when it need not be stable), and of the 8 a
#: pair has 1.4-1.5 hit children. A pair with more hit children than
#: rows goes back on the stack and is popped again: 0.21 % of killeroo's
#: expanded pairs and 0.84 % of crown-geometry's at 4 rows. Read at 3
#: on a v5e (PERF.md, PR 36): killeroo's one-chip frame 0.13 % longer,
#: crown-geometry's 0.92 % (3 % of pairs pop twice, and a wave's sparse
#: rounds sort all 8 children only under 3/8 full)
_PACK_ROWS = 4
#: a stack code's low bits hold the top-tree node; the 3 bits above them
#: the child index a put-back pair resumes at (0 for a pair popped the
#: first time), so that the code stays non-negative
_NODE_BITS = 28

_I32_MAX = np.int32(2**31 - 1)


def clear_traverse_caches() -> None:
    """Drop the jit caches of every module-level traversal entry point.

    These cache by aval shape alone, so a test that flips a trace-time
    knob (TPU_PBRT_ONEHOT, _SLAB, _HEADROOM, _LEAF_TRIS reloads) with
    unchanged shapes MUST call this or a later trace — even from a
    brand-new integrator — inlines a stale inner jaxpr."""
    for f in _TRAVERSE_JITS:
        f.clear_cache()


def _use_onehot(n_nodes: int) -> bool:
    if not cfg.onehot:
        return False
    return n_nodes <= _ONEHOT_MAX_NODES


def _flush_key_packed(n_treelets: int, ray_bits: int) -> bool:
    """FLUSH packs (treelet << ray_bits | ray) into ONE i32 sort key while
    the treelet ids, and the dead pairs' id n_treelets, fit above the ray
    bits: fewer than 4,096 treelets under the pool's 2^19-ray wave. Past
    that it sorts the pair [tid, ray] on the treelet alone."""
    return n_treelets < (1 << max(31 - ray_bits, 0))


def _flush_block(n_treelets: int, slab: int) -> int:
    """Rays per leaf block for FLUSH, from what the tracer knows before a
    ray is traced. A flush fires once 4 slabs of pairs wait (_traverse),
    and n_treelets share them: a run that long or longer fills BLOCK
    rays, a shorter one leaves the block's other slots to be fetched,
    multiplied and merged for nothing, so the block is halved while the
    mean run is below it (killeroo's ~380 treelets: 1,380 pairs a run on
    one chip, 345 on a mesh device, BLOCK; crown-geometry's 10,234: 51
    pairs, _MIN_BLOCK, where 42 / 59 / 74 % of a trip's slots hold a
    test at 128 / 64 / 32 and a block costs about what 16 slots cost:
    PERF.md, PR 32)."""
    mean_run = 4 * slab / max(n_treelets, 1)
    blk = BLOCK
    while blk > _MIN_BLOCK and mean_run < blk:
        blk //= 2
    return blk


def _flush_trip(slab: int) -> int:
    """Ray slots a trip of FLUSH's chunk loop runs, filled or not, from
    the wave's width alone. A flush fires once 4 slabs of pairs wait
    (_traverse) and the loop pays for every slot of every trip it runs
    (PERF.md, PR 32), so a trip is an eighth of a slab, rounded down to
    a power of two: a threshold flush is 32 trips or more and the empty
    tail of its last trip a small part of it, where one constant trip of
    65,536 slots made a mesh device's 131,072-pair flush three trips for
    two and a half trips' worth of blocks and gave the few thousand
    pairs of a wave's last flush a whole trip. Within _MIN_TRIP and
    _MAX_TRIP: 4,096 slots under the pool's 2^19-ray wave and on a
    mesh device of a quarter of it, 2,048 (half a slab) under the
    narrowest (PERF.md, PR 34)."""
    slots = 1 << (max(slab // 8, 1).bit_length() - 1)
    return min(max(slots, _MIN_TRIP), _MAX_TRIP)


def branch_facts(tp: TreeletPack, n_rays: int) -> dict:
    """The static facts that pick the tracer's branches for a wave of
    n_rays over this pack (`stats["telemetry"]`; the scene compiler puts
    them on its `accel/treelet_pack` span at FUSED_WAVE_RAYS)."""
    n_nodes = int(tp.top.child_idx.shape[0])
    packed = _flush_key_packed(tp.n_treelets, _ray_bits(n_rays))
    slab = _sizes(n_rays)[0]
    return {
        "stream_top_nodes": n_nodes,
        "stream_treelets": int(tp.n_treelets),
        "stream_fetch": "onehot" if _use_onehot(n_nodes) else "gather",
        "stream_flush_key": "packed" if packed else "pair",
        "stream_block": _flush_block(tp.n_treelets, slab),
        "stream_trip_slots": _flush_trip(slab),
    }


class _SState(NamedTuple):
    # Lane-major per-ray tables. Multi-row takes on this v5e cost
    # ~2 ns per fetched ELEMENT (not per index), so each consumer gets
    # its own 8-row table holding exactly what it reads, fetched in ONE
    # take: rayE for EXPAND [o(0:3) inv_d(3:6) t(6) pad], rayF for FLUSH
    # [o(0:3) d(3:6) t(6) pad]. Row 6 (the ray's current closest hit) is
    # kept identical in both: a flush's trips update it as one (R,) row
    # (1D scatters) and the flush writes it back with two contiguous
    # dynamic_update_slices (carrying a separate (R,) t array through
    # the WAVE's loop instead made XLA re-lay-out the tables every
    # iteration, ~130 ms/wave).
    rayE: jnp.ndarray  # (8, R) f32
    rayF: jnp.ndarray  # (8, R) f32
    prim: jnp.ndarray  # (R,) i32 global leaf-order triangle id, -1 miss
    stk_key: jnp.ndarray  # (W + headroom,) i32 packed (2^30 | ray<<TN | ~qtn)
    stk_code: jnp.ndarray  # (W + headroom,) i32 node | resume << _NODE_BITS
    n_stk: jnp.ndarray  # i32
    lf_ray: jnp.ndarray  # (LB + headroom,) i32 ray ids (= leaf sort keys)
    lf_tid: jnp.ndarray  # (LB + headroom,) i32 treelet ids
    n_lf: jnp.ndarray  # i32
    n_drop: jnp.ndarray  # i32 pairs lost to capacity (tests assert 0)
    n_exp: jnp.ndarray  # i32 stat: pairs expanded (every live pop)
    n_def: jnp.ndarray  # i32 stat: pairs put back for a later pop
    n_tl: jnp.ndarray  # i32 stat: (ray, treelet) block-slot tests
    n_bs: jnp.ndarray  # i32 stat: block slots the chunk loop ran
    iters: jnp.ndarray  # i32


class StreamWork(NamedTuple):
    """One traversal's work counts, read off the final `_SState`: what
    `obs/counters.py` sums into `stream_*` so a quicker wave can be told
    apart as fewer rounds or quicker rounds."""

    rounds: jnp.ndarray  # i32 EXPANDs + FLUSHes
    pairs_expanded: jnp.ndarray  # i32
    leaf_tests: jnp.ndarray  # i32 (ray, treelet) block-slot tests
    pairs_dropped: jnp.ndarray  # i32 lost to capacity (0, or false misses)
    block_slots: jnp.ndarray  # i32 slots of the flush's trips, filled or not
    #: i32 pairs with more hit children than EXPAND's sort keeps rows,
    #: put back on the stack; each is counted in pairs_expanded again
    #: when it is popped again
    pairs_deferred: jnp.ndarray


def _work(s: _SState) -> StreamWork:
    return StreamWork(s.iters, s.n_exp, s.n_tl, s.n_drop, s.n_bs, s.n_def)


def _sizes(R: int):
    """Static worklist sizes for a wave of R rays.

    Slab-size tradeoff, measured on this v5e (1M-ray camera wave):
    bigger slabs amortize per-step dispatch cost but DELAY flushes, so
    per-ray closest-t stays loose longer and the wave expands more
    pairs. The default keeps the tighter-culling small slab;
    TPU_PBRT_SLAB overrides for experiments."""
    cap = int(cfg.slab)
    slab = int(min(max(R // 4, 4096), cap))
    # TPU_PBRT_HEADROOM scales the worklist headroom (default 1.0);
    # the capacity-overflow regression test shrinks it to force drops.
    # Floors: the stack must hold at least one push burst, and the leaf
    # buffer must exceed the 8*slab flush threshold or _traverse would
    # flush empty buffers forever.
    head = float(cfg.headroom)
    w = R + max(int(24 * slab * head), slab // 2)
    lb = max(int(12 * slab * head), 9 * slab)
    return slab, w, lb


def _bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _unbits(x):
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def _ray_bits(R: int) -> int:
    rb = max(1, int(np.ceil(np.log2(max(R, 2)))))
    if rb > 29:
        raise ValueError(
            f"stream tracer waves are capped at 2^29 rays (got {R}); "
            "chunk the wave at the integrator level"
        )
    return rb


def _check_top_nodes(n_nodes: int) -> None:
    if n_nodes >= (1 << _NODE_BITS):
        raise ValueError(
            f"stream tracer top trees are capped at 2^{_NODE_BITS} nodes "
            f"(got {n_nodes}): a stack code keeps a put-back pair's "
            "resume index above the node id"
        )


def _tn_bits(R: int) -> int:
    # interior keys live in [2^30, 2^30 + 2^(rb+tn)) which must stay
    # below INT32_MAX; rb + tn <= 29 guarantees it with room to spare
    return max(0, min(12, 29 - _ray_bits(R)))


def _node_table(boxT, cidT):
    """(64, N) f32 one-hot-matmul table: rows 0..47 the 8 child boxes
    (component-major, flattened from the caller's (6, 8, N) boxT so the
    two fetch paths share one layout), rows 48..55 / 56..63 the child
    codes' low/high 16-bit halves (exact in f32; reassembled bitwise).
    +-inf box bounds are clamped to +-3e38: inf * 0.0 in the matmul
    would poison the one-hot sum with NaN."""
    N = boxT.shape[2]
    box48 = jnp.clip(boxT.reshape(48, N), -3e38, 3e38)
    lo = (cidT & 0xFFFF).astype(jnp.float32)
    hi = ((cidT >> 16) & 0xFFFF).astype(jnp.float32)
    return jnp.concatenate([box48, lo, hi], axis=0)  # (64, N)


def _fetch_children(tab64, boxT, cidT, node, use_onehot: bool):
    """Per-pair child boxes (6, 8, S) + child codes (8, S) for node ids
    (S,). Small top trees ride the MXU (one-hot matmul); big ones fall
    back to native gathers."""
    S = node.shape[0]
    N = boxT.shape[2]
    if use_onehot:
        oh = (node[None, :] == jnp.arange(N, dtype=jnp.int32)[:, None]).astype(
            jnp.float32
        )  # (N, S)
        out = jax.lax.dot(
            tab64, oh, precision=jax.lax.Precision.HIGHEST
        )  # (64, S)
        nb = out[:48].reshape(6, 8, S)
        lo = jnp.round(out[48:56]).astype(jnp.int32)
        hi = jnp.round(out[56:64]).astype(jnp.int32)
        cids = (hi << 16) | lo
    else:
        nb = jnp.take(boxT, node, axis=2)  # (6, 8, S)
        cids = jnp.take(cidT, node, axis=1)  # (8, S)
    return nb, cids


def _pack_children(key8, code8, key_in, node, resume, n_pairs, k_rows: int):
    """A slab's tested children -> the k_rows * S candidates EXPAND
    sorts: (key (k_rows * S,), code (k_rows * S,), put_back (S,)).

    key8 / code8: (8, S) every child's sort key (I32_MAX where the slab
    test missed it) and code; key_in / node / resume: (S,) what the pair
    was popped with; n_pairs: how many pairs the slab holds, in its
    first lanes. Children below the resume index were emitted by an
    earlier pop and are masked.

    Each pair's hit children are packed down the 8 rows: row j of k_rows
    holds the hit child of rank j (an exclusive prefix count down the
    rows: adds and selects on full lanes, no reduction across them),
    I32_MAX where the pair has fewer. A pair with MORE than k_rows hit
    children emits its first k_rows - 1 and, in the last row, itself:
    the key it was popped with (same ray, same quantized entry distance,
    so it sorts on top of its own children and the pop-side cull stays
    conservative for every child not yet emitted) and code = node |
    (resume' << _NODE_BITS), resume' the child INDEX of its first
    unemitted hit child. By index, not by rank: the ray's t may tighten
    between the two pops and the hit set with it, and an index can
    neither skip nor repeat a child.

    A slab with pairs in no more than k_rows / 8 of its lanes (the rounds
    at a wave's end, which cost what a full one costs) has room for all
    8 children of each: those lanes go to the sort as they are and
    nothing is put back, so fewer waves end on one more round for the
    few pairs their last rounds would have put back."""
    S = node.shape[0]
    key8 = [jnp.where(resume <= i, key8[i], _I32_MAX) for i in range(8)]
    code8 = list(code8)
    hit = [k != _I32_MAX for k in key8]
    rank = [jnp.zeros_like(node)]
    for i in range(7):
        rank.append(rank[i] + hit[i].astype(jnp.int32))
    put_back = rank[7] + hit[7].astype(jnp.int32) > k_rows
    keys, codes = [], []
    nxt = jnp.zeros_like(node)
    for j in range(k_rows):
        kj = jnp.full_like(key_in, _I32_MAX)
        cj = jnp.zeros_like(node)
        for i in range(j, 8):  # child i has rank <= i
            sel = hit[i] & (rank[i] == j)
            kj = jnp.where(sel, key8[i], kj)
            cj = jnp.where(sel, code8[i], cj)
            if j == k_rows - 1:
                nxt = jnp.where(sel, i, nxt)
        if j == k_rows - 1:  # the last row of a pair put back: itself
            kj = jnp.where(put_back, key_in, kj)
            cj = jnp.where(put_back, node | (nxt << _NODE_BITS), cj)
        keys.append(kj)
        codes.append(cj)

    lanes = k_rows * S // 8
    few = n_pairs <= lanes
    # under 8 candidates short of k_rows * S where 8 does not divide it
    tail = [jnp.full((k_rows * S - 8 * lanes,), _I32_MAX, jnp.int32)]
    return (
        jnp.where(few, jnp.concatenate([k[:lanes] for k in key8] + tail),
                  jnp.concatenate(keys)),
        jnp.where(few, jnp.concatenate([c[:lanes] for c in code8] + tail),
                  jnp.concatenate(codes)),
        put_back & ~few,
    )


def _expand(tp: TreeletPack, tab64, boxT, cidT, s: _SState, slab: int,
            w: int, lb: int, any_hit: bool, use_onehot: bool):
    R = s.rayE.shape[1]
    rb = _ray_bits(R)
    tb = _tn_bits(R)
    start = jnp.maximum(s.n_stk - slab, 0)
    k = jnp.arange(slab, dtype=jnp.int32)
    valid = k < (s.n_stk - start)
    key_in = jnp.where(
        valid, jax.lax.dynamic_slice(s.stk_key, (start,), (slab,)), _I32_MAX
    )
    code_in = jnp.where(
        valid, jax.lax.dynamic_slice(s.stk_code, (start,), (slab,)), 0
    )
    # a put-back pair's code carries the child it resumes at above the
    # node id (_pack_children)
    node = code_in & ((1 << _NODE_BITS) - 1)
    resume = code_in >> _NODE_BITS
    # stack entries are always interiors: ray id sits at key bits
    # [tb, tb+rb); the low tb bits hold the complemented quantized entry
    # distance, reconstructed here by zero-filling the mantissa tail —
    # a value <= the true t_entry, so the pop cull stays conservative
    # (carrying the exact f32 cost a third sort array + stack plane)
    rid = jnp.clip((key_in - (1 << 30)) >> tb, 0, R - 1)
    # an empty lane fetches a row too, and a take is slowest where its
    # indices agree: 28 ns an index when all fetch one row, 13 when they
    # lie far apart (PERF.md, PR 36), and a wave's last rounds are
    # mostly empty lanes
    rid = jnp.where(valid, rid, (k * 8191) % R)
    if tb:
        comp = (key_in - (1 << 30)) & ((1 << tb) - 1)
        tn_in = _unbits(((1 << tb) - 1 - comp) << (31 - tb))
    else:
        tn_in = jnp.zeros_like(key_in, jnp.float32)
    tn_in = jnp.where(valid & (key_in != _I32_MAX), tn_in, jnp.inf)
    # ONE lane-axis take covers o, inv_d AND the ray's current t
    # (per-element gather cost rules here — see rayE/rayF note)
    rows = jnp.take(s.rayE, rid, axis=1)  # (8, S)
    t_r = rows[6]
    live = valid & (key_in != _I32_MAX) & (tn_in <= t_r)
    if any_hit:
        live = live & (s.prim[rid] < 0)

    # ---- lane-major slab tests ------------------------------------------
    # Layout is everything here (profiled): all arrays keep the SLAB
    # dimension minor so every elementwise op and min/max chain runs on
    # (8, S) with full lanes and no reductions.
    if not use_onehot:
        # the native fetch is a take too (see rid): empty lanes fetch
        # nodes far apart, not all the root
        node = jnp.where(valid, node, (k * 8191) % boxT.shape[2])
    nb, cids = _fetch_children(tab64, boxT, cidT, node, use_onehot)
    ray6 = rows[0:6]  # (6, S) o + inv_d

    tx0, tx1 = slab_test_lane_major(nb[0], nb[3], ray6[0][None, :], ray6[3][None, :])
    ty0, ty1 = slab_test_lane_major(nb[1], nb[4], ray6[1][None, :], ray6[4][None, :])
    tz0, tz1 = slab_test_lane_major(nb[2], nb[5], ray6[2][None, :], ray6[5][None, :])
    tn8 = jnp.maximum(jnp.maximum(tx0, ty0), jnp.maximum(tz0, 0.0))  # (8,S)
    tf8 = jnp.minimum(jnp.minimum(tx1, ty1), jnp.minimum(tz1, t_r[None, :]))
    in_slab = tn8 <= tf8

    hit8 = live[None, :] & in_slab & (cids != _EMPTY)
    is_leaf = cids < 0

    # ---- sort-based compaction of the hit children ----------------------
    # packed i32 key (3-array int sort = the fast path; see module doc):
    # leaves first keyed by ray alone, then interiors keyed by
    # (ray, ~quantized t_entry) so each ray's nearest children end up on
    # top of the LIFO stack, dead last
    rid8 = jnp.broadcast_to(rid[None, :], cids.shape)
    # monotone 10-bit-ish quantization of the non-negative f32 tn: its
    # raw bits are order-preserving; keep the top tb bits (exponent +
    # leading mantissa). These key bits are ALL that survives: the next
    # pop's cull dequantizes them back to a conservative lower bound.
    qtn = jax.lax.shift_right_logical(_bits(tn8), 31 - tb) if tb else 0
    key_int = (1 << 30) + (rid8 << tb) + (((1 << tb) - 1) - qtn)
    key8 = jnp.where(hit8, jnp.where(is_leaf, rid8, key_int), _I32_MAX)
    code8 = jnp.where(is_leaf, decode_top_leaf(cids), cids)
    # the sort is paid by the key and most of the 8 S tested children are
    # misses: it runs over the _PACK_ROWS * S candidates the pack keeps
    key, cand_code, put_back = _pack_children(
        key8, code8, key_in, node, resume, s.n_stk - start, _PACK_ROWS
    )
    # no order is asked of equal keys (one ray's leaves; its children at
    # one quantized distance), and XLA:TPU makes a sort stable by sorting
    # an iota along: 0.70 ms against 0.43 for 2^19 keys (PERF.md, PR 36)
    key_s, code_s = jax.lax.sort([key, cand_code], num_keys=1, is_stable=False)
    n_leaf = jnp.sum(key < (1 << 30), dtype=jnp.int32)
    n_int = jnp.sum(key < _I32_MAX, dtype=jnp.int32) - n_leaf
    # a put-back only ever holds children back, so the two bounds below
    # change nothing; they are here for the sums over the (8, S) test.
    # Without a reduction over it XLA:TPU leaves the native child
    # fetch's result, and the whole slab test after it, laid out with
    # the 8 children minor, 8 of 128 lanes used: crown-geometry's frame
    # 12.5 -> 19.2 s (PERF.md, PR 36). tests/test_tpu_layout.py reads
    # the layouts the compiler chose and fails without these two sums
    n_back = jnp.sum(put_back, dtype=jnp.int32)
    n_leaf = jnp.minimum(n_leaf, jnp.sum(hit8 & is_leaf, dtype=jnp.int32))
    n_int = jnp.minimum(
        n_int, jnp.sum(hit8 & ~is_leaf, dtype=jnp.int32) + n_back
    )
    sk = _PACK_ROWS * slab

    # ---- append and push ------------------------------------------------
    # append the leaf prefix to the leaf buffer (contiguous write; for
    # leaves the sort key IS the ray id). Garbage entries past n_leaf
    # land in headroom and are overwritten or masked by n_lf.
    lf_ray = jax.lax.dynamic_update_slice(s.lf_ray, key_s, (s.n_lf,))
    lf_tid = jax.lax.dynamic_update_slice(s.lf_tid, code_s, (s.n_lf,))
    n_lf_new = s.n_lf + n_leaf
    dropped = jnp.maximum(n_lf_new - lb, 0)
    n_lf_new = jnp.minimum(n_lf_new, lb)

    # push the interior span [n_leaf, n_leaf + n_int) onto the stack: slice
    # it out of the (padded to twice their length) sorted arrays at the
    # dynamic offset, then one contiguous write at the stack top
    pad = jnp.full((sk,), _I32_MAX, jnp.int32)
    int_key = jax.lax.dynamic_slice(
        jnp.concatenate([key_s, pad]), (n_leaf,), (sk,)
    )
    int_code = jax.lax.dynamic_slice(
        jnp.concatenate([code_s, pad]), (n_leaf,), (sk,)
    )
    stk_key = jax.lax.dynamic_update_slice(s.stk_key, int_key, (start,))
    stk_code = jax.lax.dynamic_update_slice(s.stk_code, int_code, (start,))
    n_stk_new = start + n_int
    dropped = dropped + jnp.maximum(n_stk_new - w, 0)
    n_stk_new = jnp.minimum(n_stk_new, w)

    return s._replace(
        stk_key=stk_key, stk_code=stk_code, n_stk=n_stk_new,
        lf_ray=lf_ray, lf_tid=lf_tid, n_lf=n_lf_new,
        n_drop=s.n_drop + dropped,
        n_exp=s.n_exp + jnp.sum(live, dtype=jnp.int32),
        n_def=s.n_def + n_back,
        iters=s.iters + 1,
    )


def _merge_chunk(t_row, prim, rid, t_loc, k_loc, off, won, R):
    """Fold a chunk's (ray, t, prim) candidates into the per-ray best
    (t_row: every ray's closest hit so far; prim: its triangle).

    Sort the candidates on a (ray, t-bits) key pair — positive-f32 bits
    are order-preserving, so two i32 keys + the i32 payload stay on the
    int-sort fast path — then scatter only each ray-run's HEAD (its
    argmin). A few mostly-dropped scatters at sorted, unique indices
    replace the per-slot scatter-min + equality-select pair that
    dominated the round-3 profile (~12x on this v5e)."""
    prim_cand = (off[:, None] + k_loc.astype(jnp.int32)).reshape(-1)
    key_ray = jnp.where(won, rid, R).reshape(-1)
    key_t = _bits(jnp.where(won, t_loc, jnp.inf)).reshape(-1)
    r_s, t_s, p_s = jax.lax.sort([key_ray, key_t, prim_cand], num_keys=2)
    head = jnp.concatenate(
        [jnp.ones((1,), bool), r_s[1:] != r_s[:-1]]
    ) & (r_s < R)
    sel = jnp.where(head, r_s, R)
    tv = _unbits(t_s)
    # ray-run head beats the stored t iff it beats the PRE-update value
    old = t_row[jnp.clip(r_s, 0, R - 1)]
    win = head & (tv < old)
    t_row2 = t_row.at[sel].min(tv, mode="drop")
    prim2 = prim.at[jnp.where(win, r_s, R)].set(p_s, mode="drop")
    return t_row2, prim2


def _slice_rows(a, starts, width):
    """(CH,) starts -> (CH, width) contiguous slices of 1-D a, as ONE
    lax.gather with slice_sizes=(width,): on this v5e it runs as one row
    copy a start, ~0.6 us each whatever the width (2.1 M of them were
    1.26 s of a crown-geometry frame at 64 rays a block: PERF.md, PR 32),
    where a vmapped dynamic_slice unrolls into a sequential per-row loop
    (~0.8 us each, profiled). So _flush takes ONE such gather a block,
    and a lower block (_flush_block) makes more of them a trip."""
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,)
    )
    return jax.lax.gather(
        a, starts[:, None], dnums, slice_sizes=(width,),
        mode=jax.lax.GatherScatterMode.CLIP,
    )


def _cut_blocks(tid_s, n_treelets: int, blk: int, b_cap: int):
    """Cut the treelet-sorted pair buffer tid_s (dead pairs, id
    n_treelets, last) into blocks of at most blk pairs of ONE run ->
    (the first b_cap block starts, ascending, I32_MAX past the last;
    block count; live pairs). A block starts wherever the rank within
    its run is a multiple of blk and ends where the next one starts (the
    last: where the live pairs end), so a run of n pairs makes
    ceil(n / blk) blocks and the count stays under len // blk + runs."""
    idx = jnp.arange(tid_s.shape[0], dtype=jnp.int32)
    valid_s = tid_s < n_treelets
    prev = jnp.concatenate([jnp.full((1,), -1, tid_s.dtype), tid_s[:-1]])
    newrun = valid_s & (tid_s != prev)
    # the run's start under every live position: live pairs sort first,
    # so a running maximum of the run starts is the start of one's own
    run_start = jax.lax.cummax(jnp.where(newrun, idx, 0))
    brk = valid_s & ((idx - run_start) % blk == 0)
    # block b's pairs start at the position of the b-th set bit of brk:
    # one single-array int sort compacts those positions to the front
    # (searchsorted over a 1.5M-row block id was ~100x slower here)
    (start_sorted,) = jax.lax.sort(
        [jnp.where(brk, idx, _I32_MAX)], num_keys=1
    )
    return (
        start_sorted[:b_cap],
        jnp.sum(brk, dtype=jnp.int32),
        jnp.sum(valid_s, dtype=jnp.int32),
    )


def _flush(tp: TreeletPack, featT_tab, s: _SState, lb: int, blk: int,
           trip: int, any_hit: bool):
    R = s.rayE.shape[1]
    rb = _ray_bits(R)
    C = tp.n_treelets
    L = tp.leaf_tris
    # n_lf <= lb always, so the sort/scan pipeline works on the (lb,)
    # prefix — the append headroom past lb never holds countable pairs
    lb_v = min(lb, s.lf_tid.shape[0])
    b_cap = lb_v // blk + C + 2
    motion = tp.n_features == 64
    chunk = min(trip // blk, b_cap)
    # pack (treelet, ray) into one i32 sort key when the id ranges allow
    # (common case) -> single-array fast sort + ray-sorted runs; else a
    # 2-array (tid, ray) sort
    packed_key = _flush_key_packed(C, rb)

    idx = jnp.arange(lb_v, dtype=jnp.int32)
    ray_c = jnp.clip(s.lf_ray[:lb_v], 0, R - 1)
    # no flush-time t-based re-cull: it cost a (lb,)-sized random gather
    # (~40 ms/flush, the single most expensive op of the round-3 design)
    # and pruned nothing the chunk loop's per-slot t_b bound would not
    # reject anyway. Shadow waves still prune pairs whose ray has its
    # occlusion answer (one i32 gather; those pairs are pure waste).
    live = (idx < s.n_lf) & (s.lf_tid[:lb_v] >= 0)
    if any_hit:
        live = live & (s.prim[ray_c] < 0)
    if packed_key:
        key = jnp.where(
            live, (s.lf_tid[:lb_v] << rb) + ray_c, jnp.int32(C) << rb
        )
        (key_s,) = jax.lax.sort([key], num_keys=1)
        tid_s = key_s >> rb
        rid_s = key_s & ((1 << rb) - 1)
    else:
        key = jnp.where(live, s.lf_tid[:lb_v], C)
        tid_s, rid_s = jax.lax.sort([key, ray_c], num_keys=1)
    block_start, n_blocks, n_live = _cut_blocks(tid_s, C, blk, b_cap)

    def chunk_cond(c):
        return c[0] < n_blocks

    def _block_tables(cstart):
        """Per-chunk block tables, all derived from the sorted buffer
        and the block starts: one row copy a block."""
        bids = cstart + jnp.arange(chunk, dtype=jnp.int32)  # (CH,)
        # gather (not dynamic_slice): a slice's clamped start would
        # misalign starts against bids on the last chunk when n_blocks
        # approaches b_cap, silently dropping or misbinding trailing blocks
        starts = block_start[jnp.minimum(bids, b_cap - 1)]
        # the slice window is clamped to stay in bounds (slots outside
        # the block are masked by in_blk), but the treelet id MUST be
        # read at the true start: a block beginning within blk of the
        # buffer end would otherwise bind to the preceding run's treelet
        starts_w = jnp.minimum(starts, lb_v - blk)
        # a block ends where the next one starts, the last where the
        # live pairs end (b_cap holds two starts more than any flush
        # has blocks; past n_blocks a start is I32_MAX: no slot is in)
        ends = jnp.minimum(
            block_start[jnp.minimum(bids + 1, b_cap - 1)], n_live
        )
        pos = starts_w[:, None] + jnp.arange(blk, dtype=jnp.int32)
        in_blk = (pos >= starts[:, None]) & (pos < ends[:, None])
        # each block's slots are a CONTIGUOUS blk-run of the sorted
        # buffer: fetch them as ONE sliced-row gather (the mask above is
        # arithmetic on the starts so that no second one is needed) — a
        # flat gather of the same 65k positions costs ~21 ns/INDEX
        # (1.4 ms per chunk, profiled)
        rid_row = _slice_rows(rid_s, starts_w, blk)  # (CH, blk)
        rows = jnp.where(in_blk, rid_row, -1)  # (CH, blk) ray ids
        tids = jnp.where(
            bids < n_blocks, tid_s[jnp.minimum(starts, lb_v - 1)], 0
        )
        tids = jnp.clip(tids, 0, C - 1)
        return bids, rows, tids

    # The trips carry the rays' closest hits as ONE (R,) row and read
    # s.rayF as the flush found it: written back into the (8, R) tables
    # every trip, the row cost a trip 344 us at 2^17 rays whatever it
    # tested (a table copied for one row, a strided read of row 6:
    # PERF.md, PR 34), so it goes back once a flush, below.
    def chunk_body(c):
        cstart, t_row, prim, n_tl, n_bs = c
        bids, rows, tids = _block_tables(cstart)
        has_ray = rows >= 0
        rid = jnp.where(has_ray, rows, 0)
        ctr = tp.center[tids]  # (CH, 3)
        off = tp.offset[tids]  # (CH,)
        # ONE lane-axis take covers o, d AND t (see rayE/rayF note),
        # then a TRANSPOSED feature build: phi rows on axis 1, the
        # block's rays on lanes — (CH, blk, 16) would put 16 on lanes
        # (the profiled layout sin of the old path)
        rr = jnp.take(s.rayF, rid.reshape(-1), axis=1)  # (8, CH*blk)
        rrows = jnp.swapaxes(
            rr.reshape(8, chunk, blk), 0, 1
        )  # (CH, 8, blk)
        # the bound is the hit the ray had when the flush began; what an
        # earlier trip found since is held against the candidate by the
        # merge (tv < old), so the answer is the same to the bit
        t_b = jnp.where(has_ray, rrows[:, 6], -jnp.inf)  # dead: t<tm fails
        oc = [rrows[:, i] - ctr[:, i][:, None] for i in range(3)]
        dc = [rrows[:, 3 + i] for i in range(3)]
        phiT = jnp.stack(
            [oc[i] * dc[j] for i in range(3) for j in range(3)]
            + dc + oc + [jnp.ones_like(oc[0])],
            axis=1,
        )  # (CH, 16, blk)
        if motion:
            # motion packs carry 64-row cubic-in-time features: extend
            # phi with the per-ray shutter time powers (rayF row 7)
            tm_r = rrows[:, 7]  # (CH, blk)
            phiT = jnp.concatenate(
                [phiT, phiT * tm_r[:, None, :],
                 phiT * (tm_r * tm_r)[:, None, :],
                 phiT * (tm_r * tm_r * tm_r)[:, None, :]],
                axis=1,
            )  # (CH, 64, blk)
        featT = featT_tab[tids]  # (CH, F, 4L)
        out = jnp.einsum(
            "cfb,cfk->cbk", phiT, featT,
            precision=jax.lax.Precision.HIGHEST,
        )
        t_loc, k_loc, _, _ = decode_outputs(out, L, t_b)
        won = has_ray & jnp.isfinite(t_loc)  # t_loc < t[ray] by decode
        with jax.named_scope(ph.STREAM_MERGE):
            t_row2, prim2 = _merge_chunk(
                t_row, prim, rid, t_loc, k_loc, off, won, R
            )
        return (
            cstart + chunk, t_row2, prim2,
            n_tl + jnp.sum(has_ray, dtype=jnp.int32),
            n_bs + chunk * blk,
        )

    init = (jnp.int32(0), s.rayF[6], s.prim, s.n_tl, s.n_bs)
    _, t_row, prim, n_tl, n_bs = jax.lax.while_loop(
        chunk_cond, chunk_body, vary(init)
    )
    # row 6 is kept identical in both tables (see _SState)
    return s._replace(
        rayE=jax.lax.dynamic_update_slice(s.rayE, t_row[None, :], (6, 0)),
        rayF=jax.lax.dynamic_update_slice(s.rayF, t_row[None, :], (6, 0)),
        prim=prim,
        n_lf=jnp.int32(0), n_tl=n_tl, n_bs=n_bs, iters=s.iters + 1,
    )


def _traverse(tp: TreeletPack, o, d, t_max, any_hit: bool,
              time=None) -> _SState:
    R = o.shape[0]
    rb = _ray_bits(R)
    tb = _tn_bits(R)
    slab, w, lb = _sizes(R)
    s8 = 8 * slab
    blk = _flush_block(tp.n_treelets, slab)
    trip = _flush_trip(slab)
    n_nodes = int(tp.top.child_idx.shape[0])
    _check_top_nodes(n_nodes)
    use_onehot = _use_onehot(n_nodes)
    featT_tab = tp.featT  # (C, 16, 4L), stored at build
    t_max = jnp.asarray(t_max, jnp.float32)
    with jax.named_scope(ph.STREAM_SEED):
        boxT = jnp.transpose(
            jnp.concatenate([tp.top.child_bmin, tp.top.child_bmax], axis=-1),
            (2, 1, 0),
        )  # (6, 8, N)
        cidT = tp.top.child_idx.T  # (8, N)
        tab64 = _node_table(boxT, cidT) if use_onehot else None
        init = _seed(o, d, 1.0 / d, t_max, time, tb, w, lb,
                     _PACK_ROWS * slab)

    dead = t_max <= 0.0

    def cond(s: _SState):
        go = ((s.n_stk > 0) | (s.n_lf > 0)) & (s.iters < _MAX_ITERS)
        if any_hit:
            # shadow waves stop as soon as every live ray has its hit
            go = go & ~jnp.all((s.prim >= 0) | dead)
        return go

    def flush(ss: _SState):
        with jax.named_scope(ph.STREAM_FLUSH):
            return vary(_flush(tp, featT_tab, ss, lb, blk, trip, any_hit))

    def expand(ss: _SState):
        with jax.named_scope(ph.STREAM_EXPAND):
            return vary(_expand(tp, tab64, boxT, cidT, ss, slab, w, lb,
                                any_hit, use_onehot))

    def body(s: _SState):
        do_flush = (s.n_lf > lb - s8) | (s.n_stk == 0)
        # vary(): both branches must return ONE type under a mesh, and
        # each resets some counter to a replicated constant
        return jax.lax.cond(do_flush, flush, expand, s)

    with jax.named_scope(ph.STREAM_LOOP):
        return jax.lax.while_loop(cond, body, vary(init))


def _seed(o, d, inv_d, t_max, time, tb: int, w: int, lb: int,
          room: int) -> _SState:
    """The traversal's initial state: the per-ray tables and one root
    pair per live ray. room: the append headroom past w and lb, what one
    EXPAND writes at most (its sorted candidates, _PACK_ROWS * slab)."""
    R = o.shape[0]
    # the consolidated lane-major per-ray tables (see _SState.rayE/rayF);
    # rayF row 7 carries the per-ray shutter time for motion packs
    trow = (
        jnp.zeros((1, R), jnp.float32) if time is None
        else jnp.broadcast_to(
            jnp.asarray(time, jnp.float32), (R,)
        )[None, :]
    )
    pad1 = jnp.zeros((1, R), jnp.float32)
    rayE = jnp.concatenate([o.T, inv_d.T, t_max[None, :], pad1], axis=0)
    rayF = jnp.concatenate([o.T, d.T, t_max[None, :], trow], axis=0)
    alive0 = t_max > 0.0
    rid0 = jnp.arange(R, dtype=jnp.int32)
    # seed: one root pair per LIVE ray, packed exactly like _expand's
    # interior keys (tn = 0 -> qtn complement = max). Dead lanes sort to
    # the back and are excluded from n_stk — a mostly-dead bounce wave
    # pops only its live rays.
    key0 = jnp.where(
        alive0, (1 << 30) + (rid0 << tb) + ((1 << tb) - 1), _I32_MAX
    )
    (key0_s,) = jax.lax.sort([key0], num_keys=1)
    n_live = jnp.sum(alive0, dtype=jnp.int32)
    return _SState(
        rayE=rayE,
        rayF=rayF,
        prim=jnp.full((R,), -1, jnp.int32),
        stk_key=jnp.full((w + room,), _I32_MAX, jnp.int32).at[:R].set(key0_s),
        stk_code=jnp.zeros((w + room,), jnp.int32),  # root, resume 0
        n_stk=n_live,
        lf_ray=jnp.zeros((lb + room,), jnp.int32),
        lf_tid=jnp.full((lb + room,), -1, jnp.int32),
        n_lf=jnp.int32(0),
        n_drop=jnp.int32(0), n_exp=jnp.int32(0), n_def=jnp.int32(0),
        n_tl=jnp.int32(0), n_bs=jnp.int32(0), iters=jnp.int32(0),
    )


def _finalize_hits(tri_verts, o, d, t_raw, prim, time=None,
                   tri_verts1=None, tv9T=None, tv9T1=None) -> Hit:
    """(t, prim) -> full Hit: ONE tri_verts row fetch per ray recovers
    the winner's barycentrics (beats scattering b0/b1 per tested block
    slot during the merge), and the fetched vertices ride along in
    Hit.tv so shading never re-gathers them. Motion scenes lerp the
    two keyframes at the ray's time."""
    hit = prim >= 0
    t = jnp.where(hit, t_raw, jnp.inf)
    # take from a lane-major (9, T) view: the native (T, 3, 3) layout
    # gathers at ~33 ns per fetched element on this v5e, a lane-major
    # axis-1 take at ~2.6. The scene compiler bakes the (9, T) table
    # once (dev["tri_verts9T"]) — recomputing it here cost a
    # whole-triangle-table relayout copy EVERY wave
    # (JC-CHURN's sibling finding JC-RELAYOUT:stream_intersect:
    # "transpose of (T, 9) buffer"); the fallback below keeps direct
    # callers (tests, tools) working without a compiled scene.
    T = tri_verts.shape[0]
    if tv9T is None:
        tv9T = tri_verts.reshape(T, 9).T  # (9, T)
    tv = jnp.take(tv9T, jnp.maximum(prim, 0), axis=1).T.reshape(
        -1, 3, 3
    )  # (R, 3, 3)
    if tri_verts1 is not None and time is not None:
        if tv9T1 is None:
            tv9T1 = tri_verts1.reshape(T, 9).T
        tv1 = jnp.take(tv9T1, jnp.maximum(prim, 0), axis=1).T.reshape(-1, 3, 3)
        tm = jnp.asarray(time, jnp.float32).reshape(-1, 1, 1)
        tv = (1.0 - tm) * tv + tm * tv1
    v0, v1, v2 = tv[:, 0], tv[:, 1], tv[:, 2]
    e1 = v1 - v0
    e2 = v2 - v0
    pvec = jnp.cross(d, e2)
    det = jnp.sum(e1 * pvec, axis=-1)
    inv = 1.0 / jnp.where(det == 0.0, 1.0, det)
    sv = o - v0
    u = jnp.sum(sv * pvec, axis=-1) * inv
    qvec = jnp.cross(sv, e1)
    v = jnp.sum(d * qvec, axis=-1) * inv
    b0 = jnp.where(hit, 1.0 - u - v, 0.0)
    b1 = jnp.where(hit, u, 0.0)
    return Hit(t, prim, b0, b1, tv)


@jax.jit
def stream_intersect(tp: TreeletPack, tri_verts, o, d, t_max,
                     time=None, tri_verts1=None, tv9T=None,
                     tv9T1=None) -> Hit:
    """Closest hit for a flat ray batch. o, d: (R, 3); t_max scalar or
    (R,). Returns Hit with global leaf-order triangle ids (and the hit
    vertices in Hit.tv) — API-compatible with bvh_intersect /
    wide_intersect / packet_intersect. time/tri_verts1: motion blur
    (see _traverse/_finalize_hits). tv9T/tv9T1: the compile-time
    lane-major (9, T) vertex tables (dev["tri_verts9T"]); omitted, the
    relayout is recomputed per wave."""
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:-1])
    s = _traverse(tp, o, d, t_max, False, time=time)
    with jax.named_scope(ph.STREAM_FINALIZE):
        return _finalize_hits(
            tri_verts, o, d, s.rayF[6], s.prim, time=time,
            tri_verts1=tri_verts1, tv9T=tv9T, tv9T1=tv9T1,
        )


@partial(jax.jit, static_argnames=("n_finalize",))
def stream_intersect_split(tp: TreeletPack, tri_verts, o, d, t_max,
                           n_finalize: int, time=None, tri_verts1=None,
                           tv9T=None, tv9T1=None):
    """Fused-wave closest hit: traverse ALL rays, but build the full Hit
    (barycentric refetch) only for the first n_finalize — the tail (the
    integrator's queued shadow rays) needs just prim>=0, and skipping
    its per-ray tri_verts row fetch saves ~9 gathered elements/ray.
    Returns (Hit of the first n_finalize, prim ids of the tail, the
    traversal's StreamWork)."""
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:-1])
    s = _traverse(tp, o, d, t_max, False, time=time)
    n = n_finalize
    with jax.named_scope(ph.STREAM_FINALIZE):
        hit = _finalize_hits(
            tri_verts, o[:n], d[:n], s.rayF[6][:n], s.prim[:n],
            time=None if time is None else time[:n],
            tri_verts1=tri_verts1, tv9T=tv9T, tv9T1=tv9T1,
        )
    return hit, s.prim[n:], _work(s)


def stream_intersect_p(tp: TreeletPack, o, d, t_max, time=None):
    """Any-hit (shadow) predicate -> bool (R,)."""
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:-1])
    return _traverse_p(tp, o, d, t_max, time)


@jax.jit
def _traverse_p(tp: TreeletPack, o, d, t_max, time=None):
    return _traverse(tp, o, d, t_max, True, time=time).prim >= 0


@partial(jax.jit, static_argnames=("any_hit",))
def stream_traverse_stats(tp: TreeletPack, o, d, t_max, any_hit: bool = False):
    """One traversal's StreamWork alone, for the capacity audit, perf
    analysis, and the capacity-overflow regression test."""
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:-1])
    return _work(_traverse(tp, o, d, t_max, any_hit))


#: the jitted entry points clear_traverse_caches drops, bound here (not
#: looked up by name at call time) so a test that patches one of the
#: module attributes with a plain function does not break a knob flip
_TRAVERSE_JITS = (
    stream_intersect, stream_intersect_split, _traverse_p,
    stream_traverse_stats,
)
