"""Fused Pallas wavefront kernel (accel/fusedwave.py, ISSUE 9): the
TPU_PBRT_FUSED=1 flush/expand programs must be BIT-identical to the jnp
stream tracer — same EDGE_EPS band, same argmin tiebreak, same
_finalize_hits contract — with the kernels running in Pallas interpret
mode on CPU (the sequential grid semantics the TPU also guarantees).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_pbrt import config
from tpu_pbrt.accel import build as bvh_build
from tpu_pbrt.accel.treelet import build_treelet_pack


def _random_tris(n, rng, scale=0.25):
    c = rng.uniform(-2, 2, (n, 1, 3))
    return (c + rng.uniform(-scale, scale, (n, 3, 3))).astype(np.float32)


def _random_rays(n, rng):
    o = rng.uniform(-4, 4, (n, 3)).astype(np.float32)
    d = rng.normal(size=(n, 3)).astype(np.float32)
    d /= np.linalg.norm(d, axis=-1, keepdims=True)
    return jnp.asarray(o), jnp.asarray(d)


def _clear_stream_caches():
    """The stream tracer's module-level jits cache by aval shape only;
    every TPU_PBRT_FUSED flip must drop them (same seam the render
    loop's jit-key guard and audit.forced_tracer use)."""
    from tpu_pbrt.accel.stream import clear_traverse_caches

    clear_traverse_caches()


def _set_fused(monkeypatch, on: bool, **env):
    monkeypatch.setenv("TPU_PBRT_FUSED", "1" if on else "0")
    for k, v in env.items():
        monkeypatch.setenv(k, str(v))
    config.reload()
    _clear_stream_caches()


def _pack(n_tris=6000, seed=31, leaf_tris=None):
    from tpu_pbrt.accel.stream import STREAM_LEAF_TRIS

    rng = np.random.default_rng(seed)
    tris = _random_tris(n_tris, rng)
    bvh = bvh_build.build_bvh(
        *bvh_build.triangle_bounds(tris), method="sah"
    )
    tris_perm = tris[bvh.prim_order]
    tp = build_treelet_pack(
        tris_perm, bvh, leaf_tris=leaf_tris or STREAM_LEAF_TRIS
    )
    return tp, jnp.asarray(tris_perm), rng


def _both_modes(monkeypatch, fn, **env):
    """Run fn() under TPU_PBRT_FUSED=0 then =1; return both results."""
    _set_fused(monkeypatch, False, **env)
    a = fn()
    _set_fused(monkeypatch, True, **env)
    b = fn()
    _clear_stream_caches()
    return a, b


def _assert_hits_identical(h0, h1):
    t0, t1 = np.asarray(h0.t), np.asarray(h1.t)
    np.testing.assert_array_equal(t0.view(np.int32), t1.view(np.int32))
    np.testing.assert_array_equal(np.asarray(h0.prim), np.asarray(h1.prim))
    np.testing.assert_array_equal(np.asarray(h0.b0), np.asarray(h1.b0))
    np.testing.assert_array_equal(np.asarray(h0.b1), np.asarray(h1.b1))


# ---------------------------------------------------------------------------
# interpret-mode bit-identity vs the jnp stream tracer
# ---------------------------------------------------------------------------


def test_fused_bit_identity_closest_and_any_hit(monkeypatch):
    tp, tv, rng = _pack()
    o, d = _random_rays(600, rng)

    def run():
        import tpu_pbrt.accel.stream as st

        h = st.stream_intersect(tp, tv, o, d, 1e30)
        p = st.stream_intersect_p(tp, o, d, 1e30)
        stats = st.stream_traverse_stats(tp, o, d, 1e30)
        return h, np.asarray(p), [int(x) for x in stats]

    (h0, p0, s0), (h1, p1, s1) = _both_modes(monkeypatch, run)
    assert np.isfinite(np.asarray(h0.t)).sum() > 50  # the test bites
    _assert_hits_identical(h0, h1)
    np.testing.assert_array_equal(p0, p1)
    assert s0 == s1  # (n_exp, n_tl, n_drop, iters) — incl. n_drop == 0
    assert s0[2] == 0


def test_fused_bit_identity_onehot_off(monkeypatch):
    """The fused EXPAND kernel's native-take child fetch (big-top-tree
    mode) must match the jnp gather path bit-for-bit."""
    tp, tv, rng = _pack(n_tris=4000, seed=5)
    o, d = _random_rays(400, rng)

    def run():
        import tpu_pbrt.accel.stream as st

        return st.stream_intersect(tp, tv, o, d, 1e30)

    h0, h1 = _both_modes(monkeypatch, run, TPU_PBRT_ONEHOT="0")
    _assert_hits_identical(h0, h1)


def test_fused_bit_identity_motion(monkeypatch):
    """Motion packs (64-row cubic-in-time features, rayF row 7 carrying
    the shutter time) ride the fused flush kernel too."""
    rng = np.random.default_rng(7)
    tris = _random_tris(2000, rng)
    tris1 = tris + rng.uniform(-0.05, 0.05, tris.shape).astype(np.float32)
    bm = np.minimum(tris.min(axis=1), tris1.min(axis=1))
    bM = np.maximum(tris.max(axis=1), tris1.max(axis=1))
    bvh = bvh_build.build_bvh(bm, bM, method="sah")
    tp = build_treelet_pack(
        tris[bvh.prim_order], bvh, leaf_tris=256,
        tri_verts1=tris1[bvh.prim_order],
    )
    assert tp.n_features == 64
    o, d = _random_rays(256, rng)
    tm = jnp.asarray(rng.uniform(0, 1, 256).astype(np.float32))
    tv0 = jnp.asarray(tris[bvh.prim_order])
    tv1 = jnp.asarray(tris1[bvh.prim_order])

    def run():
        import tpu_pbrt.accel.stream as st

        return st.stream_intersect(
            tp, tv0, o, d, 1e30, time=tm, tri_verts1=tv1
        )

    h0, h1 = _both_modes(monkeypatch, run)
    assert np.isfinite(np.asarray(h0.t)).sum() > 20
    _assert_hits_identical(h0, h1)


def test_fused_winner_tiebreak_lower_local_index(monkeypatch):
    """Two coincident triangles produce EXACTLY equal t: the winner must
    be the lower leaf-order index, in both tracer modes (the pinned
    argmin/merge tiebreak)."""
    tri = np.asarray(
        [[[0.0, -1, -1], [0, 1, -1], [0, 0, 1]]], np.float32
    )
    # several distinct triangles + an exact duplicate pair
    rng = np.random.default_rng(3)
    filler = _random_tris(40, rng) + np.asarray([8.0, 0, 0])
    tris = np.concatenate([tri, tri, filler]).astype(np.float32)
    bvh = bvh_build.build_bvh(*bvh_build.triangle_bounds(tris))
    tris_perm = tris[bvh.prim_order]
    # one treelet holds everything (42 <= 64), so local index == leaf
    # order and the pinned tiebreak is exactly "lower leaf-order id"
    tp = build_treelet_pack(tris_perm, bvh, leaf_tris=64)
    assert tp.n_treelets == 1
    # the duplicates' leaf-order positions
    dup = sorted(int(np.where(bvh.prim_order == i)[0][0]) for i in (0, 1))
    o = jnp.asarray([[-5.0, 0, 0]])
    d = jnp.asarray([[1.0, 0, 0]])

    def run():
        import tpu_pbrt.accel.stream as st

        return st.stream_intersect(tp, jnp.asarray(tris_perm), o, d, 1e30)

    h0, h1 = _both_modes(monkeypatch, run)
    _assert_hits_identical(h0, h1)
    assert int(np.asarray(h0.prim)[0]) == dup[0]


def test_fused_empty_flush_and_dead_waves(monkeypatch):
    """Rays that (a) miss the whole scene and (b) are dead on arrival
    (t_max <= 0): the fused drain flush runs over an EMPTY leaf buffer
    (n_blocks == 0 -> zero kernel invocations) and must still agree."""
    tp, tv, rng = _pack(n_tris=1200, seed=11)
    R = 200
    o = jnp.full((R, 3), 50.0, jnp.float32)  # far outside the scene
    d = jnp.tile(jnp.asarray([1.0, 0.0, 0.0], jnp.float32), (R, 1))

    def run_miss():
        import tpu_pbrt.accel.stream as st

        return st.stream_intersect(tp, tv, o, d, 1e30)

    h0, h1 = _both_modes(monkeypatch, run_miss)
    assert (np.asarray(h0.prim) == -1).all()
    _assert_hits_identical(h0, h1)

    def run_dead():
        import tpu_pbrt.accel.stream as st

        return st.stream_intersect(tp, tv, o, d, -1.0)

    h0, h1 = _both_modes(monkeypatch, run_dead)
    assert (np.asarray(h0.prim) == -1).all()
    _assert_hits_identical(h0, h1)


def test_fused_burst_wave_small_slab(monkeypatch):
    """A small TPU_PBRT_SLAB forces the leaf buffer to cross the flush
    threshold repeatedly (multiple mid-wave flushes, the burst-wave
    shape): the fused path must stay bit-identical and drop nothing."""
    tp, tv, rng = _pack(n_tris=9000, seed=13, leaf_tris=128)
    o, d = _random_rays(4096, rng)

    def run():
        import tpu_pbrt.accel.stream as st

        h = st.stream_intersect(tp, tv, o, d, 1e30)
        stats = st.stream_traverse_stats(tp, o, d, 1e30)
        return h, [int(x) for x in stats]

    (h0, s0), (h1, s1) = _both_modes(
        monkeypatch, run, TPU_PBRT_SLAB="4096"
    )
    assert s0[3] > 3  # several expand/flush iterations actually ran
    assert s0 == s1 and s0[2] == 0
    _assert_hits_identical(h0, h1)


# ---------------------------------------------------------------------------
# integrator-level pin: pool_chunk renders bit-identical under FUSED=0/1
# ---------------------------------------------------------------------------


def test_fused_pool_chunk_bit_identity(monkeypatch):
    from tpu_pbrt.scenes import compile_api, make_killeroo_like

    def run():
        api = make_killeroo_like(
            res=16, spp=2, integrator="path", maxdepth=3,
            n_theta=24, n_phi=48,
        )
        scene, integ = compile_api(api)
        film = scene.film
        out = integ.pool_chunk(
            scene.dev, film.init_state(), jnp.int32(0), jnp.int32(0),
            256, 64, film=film, cam=scene.camera,
        )
        fs, nrays = out[0], out[1]
        return (
            [np.asarray(x) for x in jax.tree_util.tree_leaves(fs)],
            int(nrays),
        )

    (f0, r0), (f1, r1) = _both_modes(monkeypatch, run)
    assert r0 == r1 and r0 > 0
    for a, b in zip(f0, f1):
        np.testing.assert_array_equal(a, b)


def test_fused_render_reports_tracer_mode(monkeypatch):
    from tpu_pbrt.scenes import compile_api, make_killeroo_like

    _set_fused(monkeypatch, True)
    api = make_killeroo_like(
        res=12, spp=1, integrator="path", maxdepth=2,
        n_theta=24, n_phi=48,
    )
    scene, integ = compile_api(api)
    res = integ.render(scene)
    assert res.stats.get("tracer_mode") == "fused"
    _clear_stream_caches()


# ---------------------------------------------------------------------------
# gates, fallbacks, deprecation
# ---------------------------------------------------------------------------


def test_fused_gates_and_escape_hatches(monkeypatch):
    from tpu_pbrt.accel import stream as st

    # explicit on (CPU -> interpret), explicit off, VMEM ray cap,
    # and the global TPU_PBRT_PALLAS=0 escape hatch
    monkeypatch.setenv("TPU_PBRT_FUSED", "1")
    config.reload()
    assert st.tracer_mode(1 << 10) == "fused"
    assert st.tracer_mode(1 << 19) == "jnp"  # past FUSED_MAX_RAYS
    monkeypatch.setenv("TPU_PBRT_FUSED_MAX_RAYS", str(1 << 20))
    config.reload()
    assert st.tracer_mode(1 << 19) == "fused"
    monkeypatch.setenv("TPU_PBRT_PALLAS", "0")
    config.reload()
    assert st.tracer_mode(1 << 10) == "jnp"
    monkeypatch.delenv("TPU_PBRT_PALLAS")
    monkeypatch.setenv("TPU_PBRT_FUSED", "0")
    config.reload()
    assert st.tracer_mode(1 << 10) == "jnp"
    # unset = the jnp path, on every backend
    monkeypatch.delenv("TPU_PBRT_FUSED")
    config.reload()
    assert st.tracer_mode(1 << 10) == "jnp"
    # geometry helper carries the attribution fields bench.py records
    geo = st.flush_geometry(1 << 16, 64)
    assert geo["blocks_per_flush"] > 0 and geo["tracer_mode"] == "jnp"


def test_prefetch_knob_deprecated_aliases_to_fused(monkeypatch):
    monkeypatch.setenv("TPU_PBRT_PREFETCH", "1")
    with pytest.warns(DeprecationWarning, match="TPU_PBRT_PREFETCH"):
        config.reload()
    assert config.cfg.fused is True
    # an explicit TPU_PBRT_FUSED wins over the alias
    monkeypatch.setenv("TPU_PBRT_FUSED", "0")
    with pytest.warns(DeprecationWarning):
        config.reload()
    assert config.cfg.fused is False


def test_budget_pins_fused_flush_hbm_3x_below_jnp():
    """ISSUE 9 acceptance: the committed static budgets must show the
    fused flush path at least 3x below the jnp flush path in HBM bytes
    per wave (the real margin is orders of magnitude — the jnp path's
    materialized phi/feature/matmul intermediates never exist)."""
    from tpu_pbrt.analysis.cost import load_budgets

    e = load_budgets()["entries"]
    assert "stream_intersect_fused" in e and "pool_chunk_fused" in e
    assert (
        e["stream_intersect"]["hbm_bytes"]
        >= 3 * e["stream_intersect_fused"]["hbm_bytes"]
    )
    assert (
        e["pool_chunk"]["hbm_bytes"]
        >= 3 * e["pool_chunk_fused"]["hbm_bytes"]
    )
