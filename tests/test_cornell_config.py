"""cornell-path (ISSUE 27): the brute tracer on the rays the v5e got wrong,
the configuration against the plain reference, and the brute counters.

The fault was a feature matmul whose float32 the MXU did not honour at
grazing incidence (PERF.md, Findings PR 27); the brute path now tests every
(ray, triangle) pair element-wise, so (ii) below holds the program to having
no matrix product there at all.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORNELL = os.path.join(ROOT, "scenes", "cornell-path.pbrt")
CELL = "cornell-frames-1chip"


@pytest.fixture(scope="module")
def harness():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run

    yield run
    sys.path.remove(os.path.join(ROOT, "benchmark"))


@pytest.fixture(scope="module")
def cornell():
    from tpu_pbrt.scene.api import Options, compile_file

    scene, _ = compile_file(CORNELL, Options(quiet=True))
    return scene


def _camera_rays(scene):
    """Image rows 20-50 of the file's 256x256 film: the light quad at
    grazing incidence (rows 27-43) and the ceiling around it. Off the pixel
    centres, so no ray lies on a quad's diagonal."""
    from tpu_pbrt.cameras import generate_rays

    x, y = np.meshgrid(np.arange(256), np.arange(20, 51))
    pf = np.stack([x.reshape(-1) + 0.37, y.reshape(-1) + 0.61], -1).astype(np.float32)
    o, d, _ = generate_rays(scene.camera, jnp.asarray(pf), jnp.zeros_like(pf))
    return o, d, jnp.full(o.shape[:1], jnp.inf)


def _shadow_rays(scene):
    """From the ceiling's edge (0.1 mm under it, along all four walls) and
    from a grid on the floor towards points of the light quad: the quad is
    seen at a few thousandths of a radian from the first, past the blocks
    from the second. Half stop short of the light as shadow rays do, half
    run on and hit it (or the ceiling behind it)."""
    rng = np.random.default_rng(27)
    s = rng.uniform(0.01, 0.99, 512)
    edge = rng.integers(0, 4, 512)
    near = rng.uniform(0.002, 0.03, 512)
    x = np.where(edge == 0, near, np.where(edge == 1, 1 - near, s))
    z = np.where(edge == 2, near, np.where(edge == 3, 1 - near, s))
    ceil_o = np.stack([x, np.full(512, 1.0 - 1e-4), z], -1)
    floor_o = np.stack([rng.uniform(0.02, 0.98, 512), np.full(512, 1e-4), rng.uniform(0.02, 0.98, 512)], -1)
    o = np.concatenate([ceil_o, floor_o])
    target = np.stack([rng.uniform(0.35, 0.65, 1024), np.full(1024, 0.998), rng.uniform(0.35, 0.65, 1024)], -1)
    to = target - o
    dist = np.linalg.norm(to, axis=-1)
    t_max = np.where(np.arange(1024) % 2 == 0, dist * 0.999, np.inf)
    return (jnp.asarray(o, jnp.float32), jnp.asarray(to / dist[:, None], jnp.float32),
            jnp.asarray(t_max, jnp.float32))


@pytest.mark.parametrize("rays", [_camera_rays, _shadow_rays], ids=["camera_rows_20_50", "shadow_from_ceiling_edge"])
def test_brute_tracer_is_the_oracle_on_cornells_grazing_rays(cornell, rays):
    """(i) same hit set and same winner as `brute_force_intersect`."""
    from tpu_pbrt.accel.traverse import brute_force_intersect
    from tpu_pbrt.integrators.common import _closest_hit

    o, d, t_max = rays(cornell)
    got = _closest_hit(cornell.dev, o, d, t_max, None)
    tris = cornell.dev["tri_verts"][: cornell.n_tris]
    want = brute_force_intersect(tris, o, d, t_max, chunk=64)
    hit, hit_w = np.asarray(got.prim >= 0), np.asarray(want.prim >= 0)
    np.testing.assert_array_equal(hit, hit_w)
    assert 100 < hit.sum()
    light = np.flatnonzero(np.abs(np.asarray(tris)[:, :, 1] - 0.998).max(axis=1) < 1e-6)
    assert np.isin(np.asarray(want.prim), light).sum() > 50  # the grazing quad is among the winners
    np.testing.assert_allclose(np.asarray(got.t)[hit], np.asarray(want.t)[hit], rtol=1e-4, atol=1e-6)
    # one winner; where two triangles share the edge the ray runs through,
    # either may win at the same distance
    other = np.asarray(got.prim) != np.asarray(want.prim)
    assert other.sum() <= 2, np.flatnonzero(other)


def test_no_matrix_product_left_in_the_brute_path(cornell):
    """(ii) the hit decision cannot rest on the MXU's pass count: the
    lowered closest-hit of a brute scene holds no dot_general."""
    from tpu_pbrt.integrators.common import _closest_hit, scene_intersect_fused

    assert "brute" in cornell.dev and "tstream" not in cornell.dev
    o, d, t_max = _camera_rays(cornell)
    closest = jax.jit(lambda o, d, t: _closest_hit(cornell.dev, o, d, t, None)).lower(o, d, t_max)
    fused = jax.jit(lambda o, d, t: scene_intersect_fused(cornell.dev, o, d, t, n_cam=256)).lower(o, d, t_max)
    for text in (closest.as_text(), fused.as_text()):
        assert "dot_general" not in text and "convolution" not in text
    assert "brute/intersect" in closest.as_text(debug_info=True)


def test_the_cells_chunk_program_holds_no_matrix_product():
    """Camera rays, tracing, shading, film: nothing of a brute scene's
    dispatch goes through the MXU, so no pass count can enter."""
    from tpu_pbrt.scene.api import Options, compile_file

    scene, integ = compile_file(CORNELL, Options(quiet=True))
    plan = integ.prepare_chunks(scene)
    text = plan.jfn.lower(scene.film.init_state(), scene.dev, *plan.starts[0]).as_text()
    assert "stablehlo.dot_general" not in text and "stablehlo.convolution" not in text


def test_camera_rays_are_float32_true(cornell):
    """The rays of the file's camera against float64 on the host: to a
    thousandth of a pixel, and through no matrix product (at the
    TPU's default precision `p @ m.T` kept 8 bits of a raster coordinate)."""
    from tpu_pbrt.cameras import generate_rays

    x, y = np.meshgrid(np.arange(256), np.arange(256))
    pf = np.stack([x.reshape(-1) + 0.37, y.reshape(-1) + 0.61], -1).astype(np.float32)
    gen = jax.jit(lambda p: generate_rays(cornell.camera, p, jnp.zeros_like(p)))
    assert "dot_general" not in gen.lower(pf).as_text()
    o, d, _ = gen(pf)
    r2c = np.asarray(cornell.camera.raster_to_camera, np.float64)
    c2w = np.asarray(cornell.camera.camera_to_world, np.float64)
    pc = np.hstack([pf.astype(np.float64), np.zeros((len(pf), 1)), np.ones((len(pf), 1))]) @ r2c.T
    want = (pc[:, :3] / pc[:, 3:4]) @ c2w[:3, :3].T
    want /= np.linalg.norm(want, axis=-1, keepdims=True)
    pixel = 2 * np.tan(np.radians(20.0)) / 256
    assert np.abs(np.asarray(d, np.float64) - want).max() < 1e-3 * pixel
    np.testing.assert_allclose(np.asarray(o), np.broadcast_to(c2w[:3, 3], want.shape), atol=1e-6)


def test_fused_wave_returns_the_brute_work(cornell):
    from tpu_pbrt.integrators.common import scene_intersect_fused
    from tpu_pbrt.obs import counters as obs_counters

    o, d, t_max = _shadow_rays(cornell)
    t_max = t_max.at[::5].set(-1.0)  # dead lanes, as a wave has
    hit, tail, work = scene_intersect_fused(cornell.dev, o, d, t_max, n_cam=600)
    assert hit.prim.shape == (600,) and tail.shape == (424,)
    assert int(work.rays) == int((np.asarray(t_max) > 0).sum())
    ctr = obs_counters.trace_update(jax.jit(obs_counters.zeros)(), work)
    host = obs_counters.with_brute_pairs(obs_counters.to_host([ctr]), 36)
    assert host["brute_rays"] == int(work.rays) and host["stream_traversals"] == 0
    assert host["brute_pairs_tested"] == 36 * int(work.rays)
    # headroom: the product is a Python int, whatever an int32 holds
    assert obs_counters.with_brute_pairs({"brute_rays": 2**31 - 1}, 256)["brute_pairs_tested"] == 256 * (2**31 - 1)


def test_scene_writer_at_the_files_values_is_the_file(harness, cornell, tmp_path):
    """With the seeded amplitudes at 0 the writer's description compiles to
    the triangles, camera and light of scenes/cornell-path.pbrt."""
    from tpu_pbrt.scene.api import Options, compile_file

    config = harness.load_json(ROOT, "benchmark", "configs", "cornell-path.json")
    config = harness.merge(config, {"scene_params": {"seeded": {"block_rotation_deg": 0, "kd": 0, "radiance_rel": 0}}})
    desc = harness.load_module("scenes", "cornell_box").build(config, 123)
    path = harness.load_module("", "scenedesc").write_scene(desc, str(tmp_path), "scene")
    scene, _ = compile_file(path, Options(quiet=True))
    assert scene.n_tris == cornell.n_tris == config["triangles"] == 36

    def soup(s):  # order-free: each triangle's corners and reflectance, sorted as rows
        kd = np.asarray(s.dev["mat"]["kd"])[np.asarray(s.dev["tri_mat"])]
        v = np.hstack([np.asarray(s.dev["tri_verts"])[: s.n_tris].reshape(-1, 9), kd])
        return v[np.lexsort(v.T[::-1])]

    np.testing.assert_allclose(soup(scene), soup(cornell), atol=1e-6)
    for k in ("raster_to_camera", "camera_to_world"):
        np.testing.assert_allclose(np.asarray(getattr(scene.camera, k)), np.asarray(getattr(cornell.camera, k)),
                                   atol=1e-6)
    for k in ("L", "area", "twosided"):
        np.testing.assert_allclose(np.asarray(scene.dev["light"][k]), np.asarray(cornell.dev["light"][k]), rtol=1e-6)


@pytest.fixture(scope="module")
def sound(harness):
    """One frame of the cell at its `test` preset through the `frames`
    driver (a .pbrt file -> compile_file -> render), compared as the
    benchmark compares it."""
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    ctx, driver, config = harness.make_ctx(bench, CELL, 5, 0.0, False, "test")
    try:
        driver.setup(ctx)
        driver.window(ctx)
        stats = ctx["frames"][0]["stats"]
        rays = ctx["frames"][0]["rays_traced"]
        correct, rows = harness.check_film(ctx, driver, config)
    finally:
        import shutil

        shutil.rmtree(ctx["work_dir"], ignore_errors=True)
    return {"correct": correct, "rows": rows, "stats": stats, "rays": rays, "failed": ctx["failed"]}


def test_cornell_path_is_the_reference_within_the_presets_limits(sound):
    """(iii) sound."""
    assert sound["correct"] and sound["failed"] == 0, sound["rows"]


def test_cornell_path_with_the_radiance_fault_is_outside_them(harness, monkeypatch):
    """(iii) planted: radiance altered where it is deposited."""
    from tpu_pbrt.core.film import Film

    orig = Film.add_samples_pixel
    monkeypatch.setattr(
        Film, "add_samples_pixel",
        lambda self, state, px, py, L, mask, ray_weight=None: orig(self, state, px, py, L * 1.15, mask, ray_weight),
    )
    code, result = harness.run_cell(["--workload", CELL, "--seed", "5", "--seconds", "0", "--preset", "test"])
    assert code == 3 and not result["correct"]
    over = {k for k, row in result["compared"].items() if row["value"] > row["limit"]}
    assert over & {"mean_gap", "tile_gap"} and not over & {"spp_gap", "nonfinite"}


def test_brute_counters_reconcile_with_the_rays_of_a_render(sound):
    """(iv) every ray of a brute scene met every triangle."""
    c = sound["stats"]["telemetry"]["counters"]
    assert c["brute_rays"] == c["rays_traced"] == sound["rays"] > 0
    assert c["brute_pairs_tested"] == 36 * sound["rays"]
    assert c["stream_traversals"] == c["stream_leaf_tests"] == 0
    # no stream tracer: the program carries no slot count, nor pairs put back
    assert "stream_block_slots" not in c and "stream_pairs_deferred" not in c
    assert "brute_pairs_retested" not in c  # one stage: nothing is tested twice
