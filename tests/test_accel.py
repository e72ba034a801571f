"""Stage-1 geometry-kernel tests: BVH build + traversal vs brute-force
oracle, watertight intersection stress (modeled on pbrt src/tests/shapes.cpp
randomized triangle stress, SURVEY.md §4)."""

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_pbrt.accel import build as bvh_build
from tpu_pbrt.accel.traverse import (
    brute_force_intersect,
    bvh_as_device_dict,
    bvh_intersect,
    bvh_intersect_p,
    intersect_triangle,
)


def random_tris(n, rng, spread=10.0, size=1.0):
    base = rng.uniform(-spread, spread, (n, 1, 3))
    offs = rng.uniform(-size, size, (n, 3, 3))
    return (base + offs).astype(np.float32)


def random_rays(n, rng, spread=12.0):
    o = rng.uniform(-spread, spread, (n, 3)).astype(np.float32)
    d = rng.normal(size=(n, 3)).astype(np.float32)
    d /= np.linalg.norm(d, axis=1, keepdims=True)
    return o, d


@pytest.mark.parametrize("method", ["sah", "hlbvh", "middle", "equal"])
def test_bvh_matches_brute_force(method):
    rng = np.random.default_rng(7)
    tris = random_tris(300, rng)
    bmin, bmax = bvh_build.triangle_bounds(tris)
    bvh = bvh_build.build_bvh(bmin, bmax, method=method)
    tris_perm = jnp.asarray(tris[bvh.prim_order])
    dev = bvh_as_device_dict(bvh)

    o, d = random_rays(500, rng)
    o, d = jnp.asarray(o), jnp.asarray(d)
    hit_bvh = bvh_intersect(dev, tris_perm, o, d, 1e30)
    hit_bf = brute_force_intersect(tris_perm, o, d, 1e30, chunk=128)

    hit_mask_bvh = np.asarray(hit_bvh.prim >= 0)
    hit_mask_bf = np.asarray(hit_bf.prim >= 0)
    np.testing.assert_array_equal(hit_mask_bvh, hit_mask_bf)
    assert hit_mask_bf.sum() > 20, "test scene produced too few hits to be meaningful"
    np.testing.assert_allclose(
        np.asarray(hit_bvh.t)[hit_mask_bvh], np.asarray(hit_bf.t)[hit_mask_bf], rtol=1e-5, atol=1e-5
    )
    # where the nearest prim is unique, ids must agree
    same = np.asarray(hit_bvh.prim) == np.asarray(hit_bf.prim)
    assert same[hit_mask_bvh].mean() > 0.99


def test_intersect_p_consistent_with_closest_hit():
    rng = np.random.default_rng(11)
    tris = random_tris(200, rng)
    bmin, bmax = bvh_build.triangle_bounds(tris)
    bvh = bvh_build.build_bvh(bmin, bmax)
    tris_perm = jnp.asarray(tris[bvh.prim_order])
    dev = bvh_as_device_dict(bvh)
    o, d = random_rays(400, rng)
    o, d = jnp.asarray(o), jnp.asarray(d)
    closest = bvh_intersect(dev, tris_perm, o, d, 1e30)
    any_hit = bvh_intersect_p(dev, tris_perm, o, d, 1e30)
    np.testing.assert_array_equal(np.asarray(any_hit), np.asarray(closest.prim >= 0))


def test_t_max_respected():
    tri = jnp.asarray([[[0.0, -1, -1], [0, 1, -1], [0, 0, 1]]], dtype=jnp.float32)
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(np.asarray(tri)))
    dev = bvh_as_device_dict(bvh)
    o = jnp.asarray([[-5.0, 0, 0]])
    d = jnp.asarray([[1.0, 0, 0]])
    assert int(bvh_intersect(dev, tri, o, d, 10.0).prim[0]) == 0
    assert int(bvh_intersect(dev, tri, o, d, 4.0).prim[0]) == -1
    assert not bool(bvh_intersect_p(dev, tri, o, d, 4.0)[0])


def test_watertight_shared_edge():
    """Rays aimed at the shared edge of a quad's two triangles must hit
    exactly one of them (the watertight guarantee)."""
    quad = np.array(
        [
            [[0, 0, 0], [1, 0, 0], [1, 1, 0]],
            [[0, 0, 0], [1, 1, 0], [0, 1, 0]],
        ],
        dtype=np.float32,
    )
    rng = np.random.default_rng(3)
    n = 256
    # points exactly on the diagonal x=y
    s = rng.uniform(0.05, 0.95, n).astype(np.float32)
    targets = np.stack([s, s, np.zeros_like(s)], axis=1)
    o = targets + np.array([0.3, -0.2, 2.5], dtype=np.float32)
    d = targets - o
    d /= np.linalg.norm(d, axis=1, keepdims=True)
    h0, *_ = intersect_triangle(jnp.asarray(o), jnp.asarray(d), *[jnp.asarray(quad[0, i]) for i in range(3)], 1e30)
    h1, *_ = intersect_triangle(jnp.asarray(o), jnp.asarray(d), *[jnp.asarray(quad[1, i]) for i in range(3)], 1e30)
    n_hits = np.asarray(h0).astype(int) + np.asarray(h1).astype(int)
    assert (n_hits >= 1).all(), "edge rays leaked through the shared edge"


def test_barycentrics_reconstruct_point():
    rng = np.random.default_rng(5)
    tris = random_tris(50, rng)
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(tris))
    tris_perm = jnp.asarray(tris[bvh.prim_order])
    dev = bvh_as_device_dict(bvh)
    # aim rays at random triangle interiors so most rays hit
    o = rng.uniform(-15, 15, (200, 3)).astype(np.float32)
    picks = rng.integers(0, len(tris), 200)
    w = rng.dirichlet((1, 1, 1), 200).astype(np.float32)
    targets = np.einsum("nk,nkc->nc", w, tris[picks])
    d = targets - o
    d /= np.linalg.norm(d, axis=1, keepdims=True)
    o, d = jnp.asarray(o), jnp.asarray(d)
    hit = bvh_intersect(dev, tris_perm, o, d, 1e30)
    m = np.asarray(hit.prim >= 0)
    assert m.sum() > 5
    prim = np.asarray(hit.prim)[m]
    b0 = np.asarray(hit.b0)[m][:, None]
    b1 = np.asarray(hit.b1)[m][:, None]
    b2 = 1.0 - b0 - b1
    tv = np.asarray(tris_perm)[prim]
    p_bary = b0 * tv[:, 0] + b1 * tv[:, 1] + b2 * tv[:, 2]
    p_ray = np.asarray(o)[m] + np.asarray(hit.t)[m][:, None] * np.asarray(d)[m]
    np.testing.assert_allclose(p_bary, p_ray, atol=2e-3)


def test_single_and_degenerate_clusters():
    # all prims at the same centroid -> leaf fallback paths
    tri = np.tile(np.array([[[0, 0, 0], [1, 0, 0], [0, 1, 0]]], dtype=np.float32), (8, 1, 1))
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(tri))
    dev = bvh_as_device_dict(bvh)
    o = jnp.asarray([[0.2, 0.2, 5.0]])
    d = jnp.asarray([[0.0, 0.0, -1.0]])
    hit = bvh_intersect(dev, jnp.asarray(tri[bvh.prim_order]), o, d, 1e30)
    assert int(hit.prim[0]) >= 0
    np.testing.assert_allclose(float(hit.t[0]), 5.0, rtol=1e-5)


def test_morton_codes_ordering():
    pts = np.array([[0, 0, 0], [1, 1, 1], [0.49, 0.49, 0.49], [0.51, 0.51, 0.51]], dtype=np.float64)
    codes = bvh_build.morton_codes(pts, np.zeros(3), np.ones(3))
    assert codes[0] < codes[2] < codes[3] < codes[1]


def test_big_morton_build_flat_layout():
    rng = np.random.default_rng(1)
    tris = random_tris(5000, rng)
    bmin, bmax = bvh_build.triangle_bounds(tris)
    bvh = bvh_build.build_bvh(bmin, bmax, method="hlbvh", max_leaf_prims=4)
    # interior nodes: left child adjacent, second child within bounds
    # (padded empty leaves also have n_prims==0 but inverted inf bounds)
    interior = (bvh.n_prims == 0) & (bvh.second_child > 0)
    ids = np.arange(bvh.n_nodes)
    assert (bvh.second_child[interior] > ids[interior]).all()
    assert (bvh.second_child[interior] < bvh.n_nodes).all()
    # all prims appear exactly once in leaf order
    np.testing.assert_array_equal(np.sort(bvh.prim_order), np.arange(5000))
    # parent bounds contain child bounds
    sc = bvh.second_child[interior]
    assert (bvh.bounds_min[interior] <= bvh.bounds_min[interior.nonzero()[0] + 1] + 1e-6).all()
    assert (bvh.bounds_min[interior] <= bvh.bounds_min[sc] + 1e-6).all()


def test_sah_prim_order_valid():
    rng = np.random.default_rng(2)
    tris = random_tris(777, rng)
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(tris), method="sah")
    np.testing.assert_array_equal(np.sort(bvh.prim_order), np.arange(777))
    # leaves cover the full prim range without overlap
    leaves = bvh.n_prims > 0
    spans = sorted(zip(bvh.prim_offset[leaves], bvh.n_prims[leaves]))
    cursor = 0
    for off, cnt in spans:
        assert off == cursor
        cursor += cnt
    assert cursor == 777


def test_degenerate_cluster_exceeding_leaf_cap_still_all_hittable():
    """>MAX_LEAF_PRIMS distinct tris sharing one centroid must be force-split
    so the unrolled leaf loop can't silently drop primitives."""
    tris = np.array(
        [[[-s, -s, 0], [s, -s, 0], [0, 2 * s, 0]] for s in [0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8]],
        np.float32,
    )
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(tris))
    assert bvh.n_prims.max() <= bvh_build.MAX_LEAF_PRIMS
    dev = bvh_as_device_dict(bvh)
    tp = jnp.asarray(tris[bvh.prim_order])
    # point only inside the largest triangle
    h = bvh_intersect(dev, tp, jnp.asarray([[0.55, -0.55, 5]], jnp.float32), jnp.asarray([[0, 0, -1]], jnp.float32), 1e30)
    assert int(h.prim[0]) >= 0


def test_slab_nan_edge_on_ray_not_rejected():
    """Ray with d[axis]==0 and origin exactly on a node's slab plane: the
    0*inf NaN must be treated as inside-slab (pbrt's conservative ordering)."""
    tri = jnp.asarray([[[2, -1, -0.01], [2, 1, -0.01], [2, 0, 1]]], jnp.float32)
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(np.asarray(tri)))
    dev = bvh_as_device_dict(bvh)
    h = bvh_intersect(dev, tri, jnp.asarray([[0, 0, 0.0]], jnp.float32), jnp.asarray([[1, 0, 0]], jnp.float32), 1e30)
    assert int(h.prim[0]) == 0
    np.testing.assert_allclose(float(h.t[0]), 2.0, rtol=1e-5)


# -------------------------------------------------------------------------
# MXU feature-matmul leaf tests + packet/treelet traversal (accel/mxu.py,
# accel/treelet.py, accel/packet.py)
# -------------------------------------------------------------------------

def _oracle_compare(hit, hit_bf, min_hits=20):
    m = np.asarray(hit.prim >= 0)
    mb = np.asarray(hit_bf.prim >= 0)
    np.testing.assert_array_equal(m, mb)
    assert mb.sum() > min_hits
    np.testing.assert_allclose(
        np.asarray(hit.t)[m], np.asarray(hit_bf.t)[m], rtol=1e-4, atol=1e-4
    )
    same = np.asarray(hit.prim) == np.asarray(hit_bf.prim)
    assert same[m].mean() > 0.99


def test_brute_intersect_matches_oracle():
    from tpu_pbrt.accel.mxu import brute_intersect, tri_edge_table
    from tpu_pbrt.accel.traverse import brute_force_intersect

    rng = np.random.default_rng(21)
    tris = random_tris(200, rng)
    o, d = random_rays(600, rng)
    o, d = jnp.asarray(o), jnp.asarray(d)
    hf = brute_intersect(jnp.asarray(tri_edge_table(tris)), o, d, 1e30)
    hb = brute_force_intersect(jnp.asarray(tris), o, d, 1e30, chunk=256)
    _oracle_compare(hf, hb)


def test_packet_matches_oracle():
    from tpu_pbrt.accel.packet import packet_intersect, packet_intersect_p
    from tpu_pbrt.accel.traverse import brute_force_intersect
    from tpu_pbrt.accel.treelet import build_treelet_pack

    rng = np.random.default_rng(23)
    tris = random_tris(3000, rng)
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(tris), method="sah")
    tris_perm = tris[bvh.prim_order]
    tp = build_treelet_pack(tris_perm, bvh)
    assert tp.n_treelets > 8  # actually exercises the two-level walk
    o, d = random_rays(700, rng)
    o, d = jnp.asarray(o), jnp.asarray(d)
    hp = packet_intersect(tp, o, d, 1e30)
    hb = brute_force_intersect(jnp.asarray(tris_perm), o, d, 1e30, chunk=256)
    _oracle_compare(hp, hb)
    # any-hit predicate consistent with closest hit
    np.testing.assert_array_equal(
        np.asarray(packet_intersect_p(tp, o, d, 1e30)), np.asarray(hp.prim >= 0)
    )


def test_packet_t_max_respected():
    from tpu_pbrt.accel.packet import packet_intersect
    from tpu_pbrt.accel.treelet import build_treelet_pack

    tris = np.asarray([[[0.0, -1, -1], [0, 1, -1], [0, 0, 1]]], np.float32)
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(tris))
    tp = build_treelet_pack(tris[bvh.prim_order], bvh)
    o = jnp.asarray([[-5.0, 0, 0]])
    d = jnp.asarray([[1.0, 0, 0]])
    assert int(packet_intersect(tp, o, d, 10.0).prim[0]) == 0
    assert int(packet_intersect(tp, o, d, 4.0).prim[0]) == -1


def test_treelet_cut_covers_all_prims():
    from tpu_pbrt.accel.treelet import cut_treelets

    rng = np.random.default_rng(29)
    tris = random_tris(2500, rng)
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(tris), method="sah")
    off, cnt, bmin, bmax = cut_treelets(bvh)
    # treelet ranges tile [0, n) without gaps or overlap
    spans = sorted(zip(off.tolist(), cnt.tolist()))
    cursor = 0
    for o_, c_ in spans:
        assert o_ == cursor
        cursor += c_
    assert cursor == 2500


# -------------------------------------------------------------------------
# Stream (sort/compaction wavefront) traversal — accel/stream.py
# -------------------------------------------------------------------------

def test_stream_matches_oracle():
    from tpu_pbrt.accel.stream import (
        STREAM_LEAF_TRIS,
        stream_intersect,
        stream_intersect_p,
        stream_traverse_stats,
    )
    from tpu_pbrt.accel.traverse import brute_force_intersect
    from tpu_pbrt.accel.treelet import build_treelet_pack

    rng = np.random.default_rng(31)
    tris = random_tris(9000, rng)  # > 8 treelets at the 512-tri leaf default
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(tris), method="sah")
    tris_perm = tris[bvh.prim_order]
    tp = build_treelet_pack(tris_perm, bvh, leaf_tris=STREAM_LEAF_TRIS)
    assert tp.n_treelets > 8
    o, d = random_rays(700, rng)
    o, d = jnp.asarray(o), jnp.asarray(d)
    hs = stream_intersect(tp, jnp.asarray(tris_perm), o, d, 1e30)
    hb = brute_force_intersect(jnp.asarray(tris_perm), o, d, 1e30, chunk=256)
    _oracle_compare(hs, hb)
    np.testing.assert_array_equal(
        np.asarray(stream_intersect_p(tp, o, d, 1e30)), np.asarray(hs.prim >= 0)
    )
    # worklist capacity must never overflow (overflow = silent false misses)
    assert int(stream_traverse_stats(tp, o, d, 1e30).pairs_dropped) == 0


def test_stream_t_max_and_degenerate():
    from tpu_pbrt.accel.stream import STREAM_LEAF_TRIS, stream_intersect
    from tpu_pbrt.accel.treelet import build_treelet_pack

    tris = np.asarray([[[0.0, -1, -1], [0, 1, -1], [0, 0, 1]]], np.float32)
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(tris))
    tp = build_treelet_pack(tris[bvh.prim_order], bvh, leaf_tris=STREAM_LEAF_TRIS)
    o = jnp.asarray([[-5.0, 0, 0]])
    d = jnp.asarray([[1.0, 0, 0]])
    tv = jnp.asarray(tris[bvh.prim_order])
    assert int(stream_intersect(tp, tv, o, d, 10.0).prim[0]) == 0
    assert int(stream_intersect(tp, tv, o, d, 4.0).prim[0]) == -1
    # dead rays (t_max <= 0) must report misses
    assert int(stream_intersect(tp, tv, o, d, -1.0).prim[0]) == -1


def test_capacity_overflow_detected_and_loud(monkeypatch):
    """VERDICT r4 #6, two halves: (a) starved worklists really do count
    drops in-kernel; (b) a render whose audit sees drops raises unless
    the escape hatch is set."""
    import pytest

    import tpu_pbrt.integrators.common as C
    from tpu_pbrt.accel.stream import stream_traverse_stats
    from tpu_pbrt.scenes import compile_api, make_killeroo_like

    # (a) real drops: shrink the stack headroom far below a fat wave.
    # stream_traverse_stats reads the env at TRACE time — clear its jit
    # cache so earlier/later same-shape traces cannot leak sizes across
    # the env flip in either direction
    from tpu_pbrt import config

    stream_traverse_stats.clear_cache()
    monkeypatch.setenv("TPU_PBRT_HEADROOM", "0.0")
    monkeypatch.setenv("TPU_PBRT_SLAB", "4096")
    config.reload()
    api = make_killeroo_like(res=64, spp=2)
    scene, integ = compile_api(api)
    dev = scene.dev
    n = 1 << 18
    k = jnp.arange(n, dtype=jnp.int32)
    pf = jnp.stack(
        [(k % 64).astype(jnp.float32) + 0.5,
         ((k // 64) % 64).astype(jnp.float32) + 0.5], -1)
    from tpu_pbrt.cameras import generate_rays

    o, d, _ = generate_rays(scene.camera, pf, jnp.zeros_like(pf))
    drops = stream_traverse_stats(dev["tstream"], o, d, jnp.inf).pairs_dropped
    assert int(drops) > 0, "starved worklists must register drops"

    # (b) the render-side audit fails loudly on any drop (patch the
    # audit seam so this leg does not depend on chunk-size heuristics)
    monkeypatch.delenv("TPU_PBRT_HEADROOM", raising=False)
    monkeypatch.delenv("TPU_PBRT_SLAB", raising=False)
    config.reload()
    import tpu_pbrt.accel.stream as stream_mod

    real_stats = stream_mod.stream_traverse_stats
    fake = lambda *a, **kw: stream_mod.StreamWork(  # noqa: E731
        *(jnp.int32(1),) * 3, pairs_dropped=jnp.int32(7),
        block_slots=jnp.int32(1), pairs_deferred=jnp.int32(0))
    monkeypatch.setattr(stream_mod, "stream_traverse_stats", fake)
    api2 = make_killeroo_like(res=16, spp=1)
    scene2, integ2 = compile_api(api2)
    with pytest.raises(RuntimeError, match="dropped 7 traversal pairs"):
        integ2.render(scene2)
    monkeypatch.setenv("TPU_PBRT_ALLOW_DROPS", "1")
    config.reload()
    res = integ2.render(scene2)
    assert res.completed_fraction == 1.0
    monkeypatch.setattr(stream_mod, "stream_traverse_stats", real_stats)
    stream_traverse_stats.clear_cache()
