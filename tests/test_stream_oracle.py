"""The stream tracer (accel/stream.py) against an INDEPENDENT answer.

Every case of the one test below traces a wave through one entry point of
the stream tracer and holds the result to the oracle of
accel/traverse.py: every ray against every triangle, pbrt's watertight
test in plain float32 (`brute_force_intersect`; for moving triangles the
same test on each ray's own interpolated triangles). The oracle shares
nothing with the tracer: no hierarchy, no sorts, no feature product, no
EDGE_EPS band. Each case is a different lowered program: the scenes
differ in leaf size, slab size and pack width, `TPU_PBRT_ONEHOT` picks
EXPAND's child fetch (the one-hot matmul or the native gather, the
branch a top tree of more than 512 nodes takes), `key` picks FLUSH's
sort (the one packed (treelet, ray) key, or the pair [tid, ray] sorted
on the treelet alone: the branch 4,096 treelets or more take under the
pool's 2^19-ray wave, reached here by replacing the threshold
`_flush_key_packed`), `block` picks the height FLUSH cuts a treelet's
run of rays into (0: the answer of the rule `_flush_block`, 128 for
every pack here but `leaf64`'s; 64 and 32, what thousands of treelets
under one wave take, by replacing the rule), `trip` picks the blocks a
trip of FLUSH's chunk loop runs (0: the answer of the rule `_flush_trip`,
2,048 slots for these slabs of 4,096; 2 and 8 blocks by replacing the
rule, so that a flush is many trips and its last trip's starts are
clamped), and the entry picks closest hit, any hit or the pool's 2R
split wave. No case may lose a
traversal pair to worklist capacity. The scenes `fan` and `across` are
about EXPAND's packed sort: a pair with more hit children than the sort
keeps rows is put back and popped again, and costs that pop alone. The
pack itself (`_pack_children`) and the cut (`_cut_blocks`) are held to
numpy on made-up slabs and runs at the end of the file.
"""

import functools
from types import SimpleNamespace

import numpy as np
import pytest

import jax.numpy as jnp

from test_accel import _oracle_compare
from tpu_pbrt import config
from tpu_pbrt.accel import build as bvh_build
from tpu_pbrt.accel.traverse import (
    Hit,
    brute_force_intersect,
    intersect_triangle,
)
from tpu_pbrt.accel.treelet import build_treelet_pack


@pytest.fixture
def knobs(monkeypatch):
    """Set trace-time TPU_PBRT_* knobs for one case. The tracer's jitted
    entry points cache by shape alone, so every flip drops their caches."""
    from tpu_pbrt.accel.stream import clear_traverse_caches

    def set_knobs(**env):
        for k, v in env.items():
            monkeypatch.setenv(k, str(v))
        config.reload()
        clear_traverse_caches()

    yield set_knobs
    monkeypatch.undo()
    config.reload()
    clear_traverse_caches()


def _random_tris(n, rng, scale=0.25):
    c = rng.uniform(-2, 2, (n, 1, 3))
    return (c + rng.uniform(-scale, scale, (n, 3, 3))).astype(np.float32)


def _random_rays(n, rng, spread=4.0):
    o = rng.uniform(-spread, spread, (n, 3)).astype(np.float32)
    d = rng.normal(size=(n, 3)).astype(np.float32)
    d /= np.linalg.norm(d, axis=-1, keepdims=True)
    return jnp.asarray(o), jnp.asarray(d)


def _moving_oracle(tv0, tv1, o, d, t_max, time) -> Hit:
    """Every ray against every triangle AT THE RAY'S OWN TIME."""
    tm = time[:, None, None, None]
    tv = (1.0 - tm) * tv0[None] + tm * tv1[None]  # (R, T, 3, 3)
    hit, t, b0, b1 = intersect_triangle(
        o[:, None], d[:, None], tv[:, :, 0], tv[:, :, 1], tv[:, :, 2],
        jnp.broadcast_to(t_max, o.shape[:1])[:, None],
    )
    t = jnp.where(hit, t, jnp.inf)
    k = jnp.argmin(t, axis=1)
    r = jnp.arange(o.shape[0])
    found = jnp.isfinite(t[r, k])
    return Hit(
        t[r, k], jnp.where(found, k, -1).astype(jnp.int32), b0[r, k], b1[r, k]
    )


def _direct(tp, tv, tv1=None):
    """The three entry points over a hand-built pack."""
    import tpu_pbrt.accel.stream as st

    return SimpleNamespace(
        closest=lambda o, d, t, time: st.stream_intersect(
            tp, tv, o, d, t, time=time, tri_verts1=tv1),
        any=lambda o, d, t, time: st.stream_intersect_p(
            tp, o, d, t, time=time),
        split=lambda o, d, t, n, time: st.stream_intersect_split(
            tp, tv, o, d, t, n, time=time, tri_verts1=tv1),
    )


def _packed(tris, leaf_tris, rays, env=(), t_max=1e30, min_hits=20):
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(tris), method="sah")
    perm = tris[bvh.prim_order]
    tp = build_treelet_pack(perm, bvh, leaf_tris=leaf_tris)
    tv = jnp.asarray(perm)
    o, d = rays
    t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:1])
    return SimpleNamespace(
        tp=tp, fns=_direct(tp, tv), o=o, d=d, t_max=t_max, time=None,
        env=dict(env), min_hits=min_hits, order=bvh.prim_order,
        ref=brute_force_intersect(tv, o, d, t_max, chunk=256),
    )


@functools.lru_cache(maxsize=None)
def _scene(name):
    """One wave and its oracle; cases that share a scene share both."""
    from tpu_pbrt.accel.stream import STREAM_LEAF_TRIS

    if name in ("rand6000", "leaf64", "leaf128"):
        rng = np.random.default_rng(31)
        leaf = {"leaf64": 64, "leaf128": 128}.get(name, STREAM_LEAF_TRIS)
        return _packed(_random_tris(6000, rng), leaf, _random_rays(600, rng))
    if name == "burst":
        # a small slab makes the leaf buffer cross the flush threshold
        # again and again: many EXPAND / FLUSH rounds in one wave
        rng = np.random.default_rng(13)
        return _packed(
            _random_tris(9000, rng), 128, _random_rays(4096, rng),
            env={"TPU_PBRT_SLAB": 4096},
        )
    if name in ("fan", "across"):
        # 8 clusters of triangles in a row along x, one child of the top
        # tree's root each (the large ones interiors, the small ones
        # treelets). "fan": every ray runs down the row through all 8,
        # so that a pair of `_PACK_ROWS` candidate rows is put back
        # twice; "across": every ray crosses the row through one or two
        # of them, and no pair is
        rng = np.random.default_rng(36)
        tris = np.concatenate([
            _random_tris(300 if k % 2 else 100, rng, scale=0.2) * [0.2, 0.4, 0.4]
            + [3.0 * k, 0, 0]
            for k in range(8)
        ]).astype(np.float32)
        along = np.asarray([1.0, 0, 0] if name == "fan" else [0, 1.0, 0])
        # a slab of root pairs: a slab half full or less sorts every child
        n = 4096
        o = rng.uniform(-0.3, 0.3, (n, 3)) - 4.0 * along
        if name == "across":
            o[:, 0] = rng.uniform(-1.0, 22.0, n)
        d = rng.normal(size=(n, 3)) * 0.01 + along
        d /= np.linalg.norm(d, axis=-1, keepdims=True)
        sc = _packed(tris, 128, (jnp.asarray(o, jnp.float32),
                                 jnp.asarray(d, jnp.float32)), min_hits=10)
        root = np.asarray(sc.tp.top.child_idx[0])
        assert (root < 0).sum() == 4 and (root >= 0).sum() == 4
        # (ray, node) pairs the tracer of cf05afb, which sorted all 8
        # tested children and put nothing back, expanded on "across"
        # (closest hit and any hit alike: its one flush is its last round)
        sc.pairs_before_pack = 4733
        return sc
    if name == "tmax":
        # a bound of its own on every ray, a fifth of them dead on arrival
        rng = np.random.default_rng(17)
        t_max = rng.uniform(-1.5, 6.0, 600).astype(np.float32)
        return _packed(
            _random_tris(6000, rng), 256, _random_rays(600, rng), t_max=t_max)
    if name == "all-dead":
        rng = np.random.default_rng(11)
        return _packed(
            _random_tris(1200, rng), 256, _random_rays(200, rng),
            t_max=-1.0, min_hits=-1,
        )
    if name == "all-miss":
        # every ray misses the scene's bounds: the drain's flush runs over
        # an EMPTY leaf buffer
        rng = np.random.default_rng(11)
        o = jnp.full((200, 3), 50.0, jnp.float32)
        d = jnp.tile(jnp.asarray([1.0, 0.0, 0.0], jnp.float32), (200, 1))
        return _packed(_random_tris(1200, rng), 256, (o, d), min_hits=-1)
    if name == "coincident":
        # two coincident triangles give EXACTLY equal t: the winner is
        # the lower leaf-order index. One treelet holds all 42, so local
        # index == leaf order
        tri = np.asarray([[[0.0, -1, -1], [0, 1, -1], [0, 0, 1]]], np.float32)
        filler = _random_tris(40, np.random.default_rng(3)) + np.asarray(
            [8.0, 0, 0])
        tris = np.concatenate([tri, tri, filler]).astype(np.float32)
        o = jnp.asarray([[-5.0, 0, 0]], jnp.float32)
        d = jnp.asarray([[1.0, 0, 0]], jnp.float32)
        sc = _packed(tris, 64, (o, d), min_hits=0)
        assert sc.tp.n_treelets == 1
        sc.winner = min(int(np.where(sc.order == i)[0][0]) for i in (0, 1))
        return sc
    if name == "motion":
        # 64-row cubic-in-time packs; rayF's row 7 carries the shutter time
        rng = np.random.default_rng(7)
        tris = _random_tris(2000, rng)
        tris1 = tris + rng.uniform(-0.05, 0.05, tris.shape).astype(np.float32)
        bvh = bvh_build.build_bvh(
            np.minimum(tris.min(axis=1), tris1.min(axis=1)),
            np.maximum(tris.max(axis=1), tris1.max(axis=1)), method="sah",
        )
        tv0 = jnp.asarray(tris[bvh.prim_order])
        tv1 = jnp.asarray(tris1[bvh.prim_order])
        tp = build_treelet_pack(
            tris[bvh.prim_order], bvh, leaf_tris=256,
            tri_verts1=tris1[bvh.prim_order],
        )
        assert tp.n_features == 64
        o, d = _random_rays(256, rng)
        time = jnp.asarray(rng.uniform(0, 1, 256).astype(np.float32))
        t_max = jnp.full((256,), 1e30, jnp.float32)
        return SimpleNamespace(
            tp=tp, fns=_direct(tp, tv0, tv1), o=o, d=d, t_max=t_max,
            time=time, env={}, min_hits=20,
            ref=_moving_oracle(tv0, tv1, o, d, t_max, time),
        )
    assert name == "compiled"
    # through the scene compiler: `tri_verts9T` is baked and `leaf_tris`
    # comes from cfg; the oracle reads dev["tri_verts"] (leaf order)
    # without its zero-area padding rows, which the watertight test does
    # not reject once XLA contracts its edge functions into FMAs
    import tpu_pbrt.integrators.common as C
    from tpu_pbrt.scenes import compile_api, make_killeroo_like

    scene, _ = compile_api(make_killeroo_like(
        res=16, spp=1, integrator="path", maxdepth=2, n_theta=24, n_phi=48,
    ))
    dev = scene.dev
    assert "tstream" in dev and "tri_verts9T" in dev
    o, d = _random_rays(512, np.random.default_rng(19), spread=3.0)
    t_max = jnp.full((512,), 1e30, jnp.float32)
    return SimpleNamespace(
        tp=dev["tstream"], o=o, d=d, t_max=t_max, time=None, env={},
        min_hits=20,
        fns=SimpleNamespace(
            closest=lambda o, d, t, time: C.scene_intersect(dev, o, d, t),
            any=lambda o, d, t, time: C.scene_intersect_p(dev, o, d, t),
            split=lambda o, d, t, n, time: C.scene_intersect_fused(
                dev, o, d, t, n),
        ),
        ref=brute_force_intersect(
            dev["tri_verts"][: dev["tri_mat"].shape[0]], o, d, t_max,
            chunk=256),
    )


def _cases():
    for scene in ("rand6000", "burst", "motion"):
        for onehot in (1, 0):
            for entry in ("closest", "any", "split"):
                yield pytest.param(
                    scene, onehot, entry, "packed", 0, 0,
                    id=f"{scene}-onehot{onehot}-{entry}")
    # the two large-scene branches together (gather fetch + pair sort), as
    # a 3.5-million-triangle scene runs them, and the pair sort alone
    for scene, onehot in (("rand6000", 0), ("burst", 0), ("motion", 0),
                          ("rand6000", 1)):
        for entry in ("closest", "any", "split"):
            yield pytest.param(
                scene, onehot, entry, "pair", 0, 0,
                id=f"{scene}-onehot{onehot}-pair-{entry}")
    # EXPAND's put-back: all 8 children of the root hit by every ray (three
    # pops a pair at 4 candidate rows), under both child fetches, and the
    # same tree crossed so that no pair has more hit children than rows
    for scene, onehot in (("fan", 1), ("fan", 0), ("across", 1)):
        for entry in ("closest", "any", "split"):
            yield pytest.param(
                scene, onehot, entry, "packed", 0, 0,
                id=f"{scene}-onehot{onehot}-{entry}")
    for scene, entry in (
        ("leaf64", "closest"), ("leaf128", "closest"),
        ("coincident", "closest"), ("all-miss", "closest"),
        ("all-dead", "closest"), ("tmax", "closest"), ("tmax", "any"),
        ("compiled", "closest"), ("compiled", "any"), ("compiled", "split"),
    ):
        yield pytest.param(
            scene, 1, entry, "packed", 0, 0, id=f"{scene}-{entry}")
    # the lower blocks a scene of thousands of treelets takes: runs of
    # several blocks and partial last blocks under both sort keys
    for scene, key, block in (
        ("rand6000", "packed", 64), ("rand6000", "pair", 32),
        ("burst", "packed", 32), ("burst", "pair", 64),
    ):
        for entry in ("closest", "any", "split"):
            yield pytest.param(
                scene, 0, entry, key, block, 0,
                id=f"{scene}-{key}-block{block}-{entry}")
    for scene, key, block in (
        ("rand6000", "packed", 32), ("rand6000", "pair", 64),
        ("burst", "packed", 64), ("burst", "pair", 32),
    ):
        yield pytest.param(
            scene, 1, "closest", key, block, 0,
            id=f"{scene}-{key}-block{block}-closest")
    # the trip of the chunk loop, as narrow waves take it: a flush of many
    # short trips under both sort keys and at both ends of the height
    for scene, key, block, trip in (
        ("rand6000", "packed", 128, 2), ("rand6000", "pair", 32, 8),
        ("burst", "packed", 32, 2), ("burst", "pair", 128, 8),
    ):
        for entry in ("closest", "any", "split"):
            yield pytest.param(
                scene, 0, entry, key, block, trip,
                id=f"{scene}-{key}-block{block}-trip{trip}-{entry}")
    for scene, key, block, trip in (
        ("rand6000", "packed", 32, 8), ("rand6000", "pair", 128, 2),
        ("burst", "packed", 128, 8), ("burst", "pair", 32, 2),
    ):
        yield pytest.param(
            scene, 1, "closest", key, block, trip,
            id=f"{scene}-{key}-block{block}-trip{trip}-closest")


@pytest.mark.parametrize("scene,onehot,entry,key,block,trip", list(_cases()))
def test_stream_tracer_matches_oracle(scene, onehot, entry, key, block, trip,
                                      knobs, monkeypatch):
    import tpu_pbrt.accel.stream as st
    from tpu_pbrt.accel.stream import _ONEHOT_MAX_NODES, stream_traverse_stats

    sc = _scene(scene)
    if key == "pair":
        # no pack a test can trace has 4,096 treelets: move the threshold
        # (before `knobs` drops the jit caches; its teardown undoes both)
        monkeypatch.setattr(st, "_flush_key_packed", lambda n, rb: False)
    if block:
        # nor 10,234: replace the rule that reads the treelet count
        monkeypatch.setattr(st, "_flush_block", lambda n, slab: block)
    if trip:
        # nor a flush of more than two trips of the rule's: shorten the trip
        monkeypatch.setattr(st, "_flush_trip", lambda slab: trip * block)
    knobs(TPU_PBRT_ONEHOT=onehot, **sc.env)
    # a top tree this small takes the one-hot fetch unless told otherwise
    assert sc.tp.top.child_idx.shape[0] <= _ONEHOT_MAX_NODES
    facts = st.branch_facts(sc.tp, sc.o.shape[0])
    assert facts["stream_flush_key"] == key
    block = block or (64 if scene == "leaf64" else 128)
    assert facts["stream_block"] == block
    trip_slots = facts["stream_trip_slots"]
    assert trip_slots == (trip * block if trip else 2048)
    assert facts["stream_fetch"] == ("onehot" if onehot else "gather")
    o, d, t_max, ref = sc.o, sc.d, sc.t_max, sc.ref
    ref_hit = np.asarray(ref.prim) >= 0
    if entry == "split":
        n = o.shape[0] // 2
        head, tail, work = sc.fns.split(o, d, t_max, n, sc.time)
        _oracle_compare(
            head, Hit(ref.t[:n], ref.prim[:n], None, None), sc.min_hits // 2)
        tail = np.asarray(tail)
        np.testing.assert_array_equal(tail >= 0, ref_hit[n:])
        same = tail == np.asarray(ref.prim)[n:]
        assert same[ref_hit[n:]].mean() > 0.99
        # every test ran in a slot, and the loop runs whole trips
        assert 0 < int(work.leaf_tests) <= int(work.block_slots)
        assert int(work.block_slots) % trip_slots == 0
    else:
        if entry == "closest":
            hit = sc.fns.closest(o, d, t_max, sc.time)
            if sc.min_hits < 0:  # nothing may hit, in either answer
                assert not ref_hit.any() and (np.asarray(hit.prim) == -1).all()
            else:
                _oracle_compare(hit, ref, sc.min_hits)
            if scene == "coincident":
                assert int(hit.prim[0]) == int(ref.prim[0]) == sc.winner
        else:
            occluded = np.asarray(sc.fns.any(o, d, t_max, sc.time))
            np.testing.assert_array_equal(occluded, ref_hit)
            assert ref_hit.sum() > sc.min_hits
        # (no shutter time here: a moving pack is counted at time 0)
        work = stream_traverse_stats(
            sc.tp, o, d, t_max, any_hit=entry == "any")
    assert int(work.pairs_dropped) == 0
    if scene == "burst":
        assert int(work.rounds) > 3
    if scene == "fan":
        # every root pair is put back, and pops again
        assert int(work.pairs_deferred) > o.shape[0]
    if scene == "across":
        assert int(work.pairs_deferred) == 0
        assert int(work.pairs_expanded) == sc.pairs_before_pack


@pytest.mark.parametrize("resume", range(8))
@pytest.mark.parametrize("fill", ["full", "sparse"])
@pytest.mark.parametrize("k_rows", [3, 4])
def test_pack_children_against_numpy(k_rows, fill, resume):
    """The pack alone, over every one of the 256 hit sets a pair can have
    (each under two leaf / interior mixes), popped with `resume` and then
    again and again for as long as it is put back, the first mix's hit
    sets SHRINKING between pops as a tightened t shrinks them. Against a
    numpy model of one pop: row j holds the hit child of rank j at or
    past the resume index, a pair with more of them than rows all but
    one and itself; a slab with pairs in no more than k_rows / 8 of its
    lanes goes to the sort as it is, 8 children a pair. Over the chain:
    no child below the first resume index is emitted, none twice, and
    every child that stays hit is emitted exactly once."""
    import jax

    from tpu_pbrt.accel.stream import _NODE_BITS, _pack_children

    rng = np.random.default_rng(8 * k_rows + resume)
    big = np.iinfo(np.int32).max
    n = 512  # pairs, in the first lanes of the slab
    S = n if fill == "full" else -(-8 * n // k_rows)
    lanes = k_rows * S // 8
    assert (lanes >= n) == (fill == "sparse")
    hit = np.zeros((8, S), bool)
    hit[:, :n] = ((np.arange(n)[None, :] % 256) >> np.arange(8)[:, None]) & 1
    leaf = rng.random((8, S)) < 0.4
    ray = np.arange(S, dtype=np.int32)
    # keys and codes that name their pair and child: no two alike
    key8 = np.where(leaf, ray, (1 << 30) + (ray << 4) + np.arange(8)[:, None])
    key8 = key8.astype(np.int32)
    code8 = (ray * 8 + np.arange(8)[:, None]).astype(np.int32)
    key_in = ((1 << 30) + (ray << 4) + 15).astype(np.int32)
    node = rng.integers(0, 1 << _NODE_BITS, S).astype(np.int32)
    pack = jax.jit(_pack_children, static_argnums=6)

    def model(hit, res):
        """-> keys, codes, [(pair, child emitted)], {pair put back: the
        child it resumes at}"""
        keys = np.full(k_rows * S, big, np.int64)
        codes = np.zeros(k_rows * S, np.int64)
        out, back = [], {}
        for p in range(n):
            kids = [i for i in range(8) if hit[i, p] and i >= res[p]]
            if fill == "sparse":  # child i of the pair in lane p, as tested
                at = [i * lanes + p for i in kids]
            else:  # rank j of the pair in lane p
                at = [j * S + p for j in range(min(len(kids), k_rows))]
            if len(kids) > len(at):
                back[p] = kids[k_rows - 1]
                kids = kids[: k_rows - 1]
                keys[at[-1]] = key_in[p]
                codes[at[-1]] = node[p] | (back[p] << _NODE_BITS)
            out += [(p, i) for i in kids]
            for a, i in zip(at, kids):
                keys[a], codes[a] = key8[i, p], code8[i, p]
        return keys, codes, out, back

    res = np.full(S, resume, np.int32)
    waiting = np.arange(S) < n  # pairs on the stack
    emitted = np.zeros((8, S), int)
    pops = 0
    while waiting.any():
        key, code, back = (np.asarray(x) for x in pack(
            jnp.asarray(np.where(hit & waiting, key8, big)),
            jnp.asarray(code8), jnp.asarray(key_in),
            jnp.asarray(node), jnp.asarray(res), jnp.int32(n), k_rows))
        want_key, want_code, out, again = model(hit & waiting, res)
        np.testing.assert_array_equal(key, want_key)
        np.testing.assert_array_equal(code[key != big], want_code[key != big])
        assert sorted(np.nonzero(back)[0]) == sorted(again)
        for p, i in out:
            emitted[i, p] += 1
        for p, i in again.items():
            assert res[p] < i < 8
            res[p] = i
        waiting = back
        # t tightened: fewer hits (the second mix keeps all of its own)
        hit = hit & ((rng.random((8, S)) < 0.9) | (np.arange(S) >= 256))
        pops += 1
    assert emitted.max() == 1 and not emitted[:resume].any()
    # what never stopped being hit came out, whatever else was culled
    assert (emitted[resume:][hit[resume:]] == 1).all()
    # the pair with every child hit: k_rows - 1 a pop, k_rows the last
    full = 1 + max(0, -(-(8 - resume - k_rows) // (k_rows - 1)))
    assert pops == (full if fill == "full" else 1)


@pytest.mark.parametrize("blk", [128, 64, 32])
def test_cut_blocks_against_numpy(blk):
    """The cut on made-up sorted runs: every live pair in exactly one
    block, no block over two treelets or over `blk` pairs, and as many
    blocks as the runs need, sum of ceil(n_run / blk)."""
    import jax

    from tpu_pbrt.accel.stream import _cut_blocks

    rng = np.random.default_rng(blk)
    C = 40
    # runs of 0, 1, blk - 1, blk, blk + 1, 3 * blk and anything between
    lens = np.concatenate([
        [0, 1, blk - 1, blk, blk + 1, 3 * blk, 0, 2 * blk + 5],
        rng.integers(0, 3 * blk, C - 8),
    ])
    tid = np.repeat(np.arange(C), lens).astype(np.int32)
    n_live, n = tid.size, tid.size + 77
    tid_s = np.concatenate([tid, np.full(n - n_live, C, np.int32)])
    b_cap = n // blk + C + 2
    starts, n_blocks, live = jax.jit(_cut_blocks, static_argnums=(1, 2, 3))(
        jnp.asarray(tid_s), C, blk, b_cap)
    starts = np.asarray(starts)
    want = int(np.sum(-(-lens // blk)))
    assert int(n_blocks) == want <= b_cap - 2 and int(live) == n_live
    assert (starts[want:] == np.iinfo(np.int32).max).all()
    # a block runs from its start to the next one's (the last: to the end
    # of the live pairs): together they tile [0, n_live), each pair once
    edges = np.append(starts[:want], n_live)
    assert edges[0] == 0 and (np.diff(edges) >= 1).all()
    for a, b in zip(edges[:-1], edges[1:]):
        assert b - a <= blk and len(set(tid[a:b])) == 1
        # full but for its run's last block
        assert b - a == blk or b == n_live or tid[b] != tid[a]


@pytest.mark.parametrize("blk", [128, 32])
@pytest.mark.parametrize("over", [0, 1], ids=["exact", "one-over"])
def test_flush_last_trip(over, blk):
    """FLUSH alone over a made-up leaf buffer: every one of 2 * blk rays
    paired with EVERY treelet is two full blocks a treelet, so the answer
    is the oracle's closest hit. The trip is set so that the blocks are an
    exact number of trips, and so that they are one block over: the last
    trip then runs one block, and since the buffer is exactly full its
    other block ids lie past the table of starts (a slice of that table
    in the gather's place is clamped there, and fails this case)."""
    import jax

    import tpu_pbrt.accel.stream as st

    sc = _scene("rand6000")
    tp, C, n = sc.tp, sc.tp.n_treelets, 2 * blk
    o, d, t_max = sc.o[:n], sc.d[:n], sc.t_max[:n]
    slab, w, _ = st._sizes(n)
    lb = n * C
    chunk = 2 * C - 1 if over else C
    assert C > 4  # so that the second trip ends past the lb // blk + C + 2 starts

    @jax.jit
    def run(o, d, t_max):
        s = st._seed(o, d, 1.0 / d, t_max, None, st._tn_bits(n), w, lb,
                     st._PACK_ROWS * slab)
        # treelet-minor, so that the flush's sort has something to do
        k = jnp.arange(n * C, dtype=jnp.int32)
        s = s._replace(
            lf_ray=s.lf_ray.at[: n * C].set(k // C),
            lf_tid=s.lf_tid.at[: n * C].set(k % C), n_lf=jnp.int32(n * C))
        s = st._flush(tp, tp.featT, s, lb, blk, chunk * blk, False)
        return s.rayF[6], s.prim, s.n_tl, s.n_bs, s.n_lf

    t, prim, n_tl, n_bs, n_lf = run(o, d, t_max)
    ref = Hit(sc.ref.t[:n], sc.ref.prim[:n], None, None)
    hit = np.asarray(prim) >= 0
    _oracle_compare(
        Hit(jnp.where(hit, t, jnp.inf), prim, None, None), ref, blk // 8)
    assert int(n_lf) == 0 and int(n_tl) == n * C
    assert int(n_bs) == 2 * chunk * blk
