"""Distribution-layer tests on the virtual 8-device CPU mesh (SURVEY.md §4:
how multi-node is tested without a cluster). Validates that the shard_map
tile scheduler + psum film merge produces the same image as the
single-device path — the distributed film merge is exact, not approximate,
because work items are partitioned (each sample is computed exactly once,
on exactly one device)."""

import jax
import numpy as np
import pytest

from tpu_pbrt.parallel.mesh import make_mesh
from tpu_pbrt.scenes import compile_api, make_cornell

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh from conftest"
)


def test_mesh_shape():
    mesh = make_mesh(8)
    assert mesh.devices.size == 8
    assert mesh.axis_names == ("tiles",)


@pytest.mark.parametrize("short_last", [False, True], ids=["whole", "short-last"])
@pytest.mark.parametrize("per_dev", [1, 3, 64, 288, 1024, 4097, 1 << 18])
@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_work_item_deals_every_item_of_a_dispatch_once(n_dev, per_dev, short_last):
    """The map from a device's local work counter to the dispatch's work
    index (parallel/mesh.work_item) is a partition of every dispatch, for
    every share a plan can have (powers of two, 288, odd, smaller than a
    granule), the identity on one device, and stays inside int32 as
    work_to_rays applies it at more than 2^31 work items."""
    from tpu_pbrt.parallel.mesh import GRANULE_PIXELS, work_granule, work_item

    spp = 16
    g = work_granule(per_dev, spp, n_dev)
    assert per_dev % g == 0
    assert g == per_dev if n_dev == 1 else g <= GRANULE_PIXELS * spp
    k = np.arange(per_dev, dtype=np.int32)
    if n_dev == 1:
        assert work_item(k, 0, n_dev, g) is k  # nothing to trace
    chunk = per_dev * n_dev
    shares = [work_item(k, i, n_dev, g) for i in range(n_dev)]
    assert all(sh.dtype == np.int32 for sh in shares)
    assert np.array_equal(np.sort(np.concatenate(shares)), np.arange(chunk))
    for i, sh in enumerate(shares):
        # the host's half (the start pair) and the device's half add up
        assert np.array_equal(
            sh, work_item(0, i, n_dev, g) + work_item(k, 0, n_dev, g)
        )

    # the first and the last dispatch of a render of more than 2^31 work
    # items, through work_to_rays' own arithmetic on the int32 start pair
    npix = chunk * -(-(1 << 27) // chunk) + (3 if short_last else 0)
    total = npix * spp
    assert total >= 1 << 31
    n_chunks = -(-total // chunk)
    for c in (0, n_chunks - 1):
        got = []
        for i in range(n_dev):
            start_pix, start_s = divmod(c * chunk + work_item(0, i, n_dev, g), spp)
            assert 0 <= start_pix < (1 << 31) and 0 <= start_s < spp
            s_tot = np.int64(start_s) + work_item(k, 0, n_dev, g)
            pix = np.int64(start_pix) + s_tot // spp
            assert s_tot.max() < (1 << 31) and pix.max() < (1 << 31)
            valid = pix < npix
            got.append((pix * spp + s_tot % spp)[valid])
        lo, hi = c * chunk, min((c + 1) * chunk, total)
        assert np.array_equal(np.sort(np.concatenate(got)), np.arange(lo, hi))
    if short_last:
        assert hi - lo < chunk or chunk <= 3 * spp


def _corner_quad(res=32, spp=4, sampler=None):
    """A lit matte quad in the bottom rows of the image's left half and
    nothing else: a path that meets it goes on (a shadow ray, a bounce),
    one that misses ends with its camera ray. The work index is
    pixel-major, so a device given consecutive items of the image's last
    rows traces twice what a device given its first rows does."""
    from tpu_pbrt.scene.api import Options, parse_string, pbrt_init

    if sampler is None:
        sampler = f'Sampler "zerotwosequence" "integer pixelsamples" [{spp}]'
    api = pbrt_init(Options(quiet=True))
    parse_string(f'''
Integrator "path" "integer maxdepth" [3]
{sampler}
PixelFilter "box"
Film "image" "integer xresolution" [{res}] "integer yresolution" [{res}] "string filename" [""]
LookAt 0 0 -3  0 0 0  0 1 0
Camera "perspective" "float fov" [40]
WorldBegin
LightSource "point" "rgb I" [6 6 6] "point from" [0 0 -3]
Material "matte" "rgb Kd" [0.7 0.7 0.7]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-1.05 -1.05 0  -0.05 -1.05 0  -0.05 -0.55 0  -1.05 -0.55 0]
''', api, render=False)
    return compile_api(api)


#: the most the devices' ray counts may differ by, (max - min) / mean, on
#: the corner scene; the parent's slicing (one granule a device) reads 0.377, the granules 0.034
RAY_SPREAD_LIMIT = 0.1


@pytest.mark.parametrize("scene_of", ["cornell", "corner-quad"])
def test_sharded_render_matches_single_device(monkeypatch, scene_of):
    from tpu_pbrt import config
    from tpu_pbrt.parallel import mesh as pmesh

    def build():
        if scene_of == "cornell":
            return compile_api(
                make_cornell(res=24, spp=8, integrator="path", maxdepth=3)
            )
        return _corner_quad()

    # two chunks, so that the second dispatch sees the first one's output
    chunk = 24 * 24 * 4 if scene_of == "cornell" else 32 * 32 * 2
    monkeypatch.setenv("TPU_PBRT_CHUNK", str(chunk))
    config.reload()
    scene, integ = build()
    r_single = integ.render(scene)
    tel = r_single.stats["telemetry"]
    assert tel["ray_spread"]["per_device_rays"] == [r_single.rays_traced]
    assert tel["ray_spread"]["rel_spread"] == 0.0

    n_dev = 8 if scene_of == "cornell" else 4
    scene2, integ2 = build()
    r_mesh = integ2.render(scene2, mesh=make_mesh(n_dev))
    # the merged film comes back replicated over the mesh; the program
    # must not be built a second time for it (it was, on four chips)
    assert r_mesh.stats["programs_after_first_chunk"] == 0
    assert r_single.stats["programs_after_first_chunk"] == 0

    assert r_mesh.image.shape == r_single.image.shape
    assert r_mesh.image.max() > 0
    # identical sample set, partitioned across devices -> identical film up
    # to float addition order
    assert np.allclose(r_mesh.image, r_single.image, rtol=1e-4, atol=1e-5)
    assert r_mesh.rays_traced == r_single.rays_traced
    rays = r_mesh.stats["telemetry"]["ray_spread"]
    assert sum(rays["per_device_rays"]) == r_mesh.rays_traced
    assert len(rays["per_device_rays"]) == n_dev
    if scene_of == "cornell":
        return
    assert rays["rel_spread"] < RAY_SPREAD_LIMIT, rays

    # the parent's slicing is one granule a device: the same items, the
    # same film, and a spread that breaks the limit several times over
    monkeypatch.setattr(
        pmesh, "work_granule", lambda per_dev, spp, n_dev: per_dev
    )
    scene3, integ3 = build()
    plan = integ3.prepare_chunks(scene3, make_mesh(n_dev))
    assert np.asarray(plan.starts[1]).tolist() == [
        list(divmod(chunk + i * plan.per_dev, plan.spp)) for i in range(n_dev)
    ]
    r_sliced = integ3.render(scene3, mesh=make_mesh(n_dev))
    assert r_sliced.rays_traced == r_single.rays_traced
    assert np.allclose(r_sliced.image, r_single.image, rtol=1e-4, atol=1e-5)
    sliced = r_sliced.stats["telemetry"]["ray_spread"]
    assert sliced["rel_spread"] > 3 * RAY_SPREAD_LIMIT, sliced


def test_sharded_halton_render_matches_single_device(monkeypatch):
    """ISSUE 33: `Sampler "halton"` on one device against a file with NO
    Sampler line (upstream's default: halton, 16 samples a pixel) under a
    mesh of four, where each device's lanes pick their own pairs of prime
    bases: both through the pool, the same film."""
    from tpu_pbrt import config

    monkeypatch.setenv("TPU_PBRT_CHUNK", str(32 * 32 * 16 // 2))
    config.reload()
    scene, integ = _corner_quad(sampler='Sampler "halton" "integer pixelsamples" [16]')
    r_single = integ.render(scene)
    scene2, integ2 = _corner_quad(sampler="")
    assert integ2.skind == "halton" and integ2.spp == 16
    r_mesh = integ2.render(scene2, mesh=make_mesh(4))
    pairs = [r.stats["telemetry"]["counters"]["halton_pairs"] for r in (r_single, r_mesh)]
    for r in (r_single, r_mesh):
        assert r.stats["regen"] and r.stats["programs_after_first_chunk"] == 0
    assert r_single.image.max() > 0
    assert np.allclose(r_mesh.image, r_single.image, rtol=1e-4, atol=1e-5)
    assert r_mesh.rays_traced == r_single.rays_traced
    assert pairs[0] == pairs[1] > 0
    assert len(r_mesh.stats["telemetry"]["ray_spread"]["per_device_rays"]) == 4


def test_checkpoint_cut_under_contiguous_slices_resumes_to_the_same_film(
    monkeypatch, tmp_path
):
    """A checkpoint is cut at a dispatch boundary and a dispatch covers
    the same work items whoever draws which: one that a program with
    contiguous per-device slices wrote (the parent's) resumes under the
    round-robin granules to the film of an uninterrupted render."""
    from tpu_pbrt import config
    from tpu_pbrt.parallel import mesh as pmesh
    from tpu_pbrt.parallel.checkpoint import save_checkpoint

    monkeypatch.setenv("TPU_PBRT_CHUNK", str(32 * 32 * 2))
    config.reload()
    mesh = make_mesh(4)
    scene, integ = _corner_quad()
    whole = integ.render(scene, mesh=mesh)

    with monkeypatch.context() as m:
        m.setattr(pmesh, "work_granule", lambda per_dev, spp, n_dev: per_dev)
        scene1, integ1 = _corner_quad()
        plan = integ1.prepare_chunks(scene1, mesh)
        assert plan.n_chunks == 2
        state, aux = plan.dispatch(scene1.film.init_state(), 0)
        ck = str(tmp_path / "film.ckpt")
        save_checkpoint(
            ck, state, 1, int(plan.aux_parts(aux)[0]),
            fingerprint=plan.fingerprint,
        )

    scene2, integ2 = _corner_quad()
    resumed = integ2.render(scene2, mesh=mesh, checkpoint_path=ck)
    # it drew the second dispatch only, on top of the loaded film
    assert 0 < resumed.stats["n_waves"] < whole.stats["n_waves"]
    assert resumed.rays_traced == whole.rays_traced
    assert np.allclose(resumed.image, whole.image, rtol=1e-4, atol=1e-5)


def test_sharded_render_four_devices():
    api = make_cornell(res=16, spp=4, integrator="directlighting", maxdepth=2)
    scene, integ = compile_api(api)
    r = integ.render(scene, mesh=make_mesh(4))
    assert r.image.max() > 0


class TestFaultInjection:
    """Worker-failure handling (SURVEY.md §2e): dropped chunk dispatches
    are re-dispatched; a state-poisoning failure rolls back to the last
    checkpoint. Both recoveries must be BIT-identical to the undisturbed
    render (chunks are idempotent pure functions of the work range).

    ISSUE 5 migrated the injections from the old per-integrator
    `_fault_hook` monkeypatch onto the first-class chaos registry
    (tpu_pbrt/chaos) — the same seam `python -m tpu_pbrt.chaos`
    exercises matrix-wide."""

    def _scene(self):
        api = make_cornell(res=16, spp=8, integrator="path", maxdepth=2)
        return compile_api(api)

    def test_redispatch_bit_identical(self):
        from tpu_pbrt.chaos import CHAOS

        scene, integ = self._scene()
        # small chunks so the render has several dispatches
        import os

        from tpu_pbrt import config

        os.environ["TPU_PBRT_CHUNK"] = str(16 * 16 * 2)
        os.environ["TPU_PBRT_RETRY_BACKOFF"] = "0.01"
        config.reload()
        try:
            ref = integ.render(scene)

            scene2, integ2 = self._scene()
            CHAOS.install("dispatch:fail@chunk=1&attempt=0")
            r = integ2.render(scene2)
            assert CHAOS.fired_total() == 1, "fault never fired"
            assert r.stats["recovery"]["redispatches"] == 1
        finally:
            CHAOS.clear()
            del os.environ["TPU_PBRT_CHUNK"]
            del os.environ["TPU_PBRT_RETRY_BACKOFF"]
        np.testing.assert_array_equal(np.asarray(r.image), np.asarray(ref.image))
        assert r.rays_traced == ref.rays_traced

    def test_poisoned_state_recovers_via_checkpoint(self, tmp_path):
        from tpu_pbrt.chaos import CHAOS

        import os

        from tpu_pbrt import config

        os.environ["TPU_PBRT_CHUNK"] = str(16 * 16 * 2)
        os.environ["TPU_PBRT_RETRY_BACKOFF"] = "0.01"
        config.reload()
        try:
            scene, integ = self._scene()
            ref = integ.render(scene)

            scene2, integ2 = self._scene()
            ck = str(tmp_path / "film.ckpt")
            CHAOS.install("dispatch:poison@chunk=3")
            r = integ2.render(scene2, checkpoint_path=ck, checkpoint_every=1)
            assert CHAOS.fired_total() == 1
            assert r.stats["recovery"]["rollbacks"] == 1
        finally:
            CHAOS.clear()
            del os.environ["TPU_PBRT_CHUNK"]
            del os.environ["TPU_PBRT_RETRY_BACKOFF"]
        np.testing.assert_allclose(
            np.asarray(r.image), np.asarray(ref.image), rtol=1e-6, atol=1e-7
        )

    def test_build_refusal_fails_once_with_the_compilers_words(self):
        """A dispatch that was still building its program and raised will
        raise again on every attempt: it surfaces ONCE, as itself, and
        never enters the re-dispatch ladder. The same error out of a
        dispatch that only executed is a device loss for the ladder."""
        from tpu_pbrt.integrators.common import ChunkCompileError
        from tpu_pbrt.obs.compiles import COMPILES

        scene, integ = self._scene()
        plan = integ.prepare_chunks(scene)
        calls = []

        def refuses(state, dev, *args):
            calls.append(args)
            COMPILES.traces += 1  # what jax records while building
            raise jax.errors.JaxRuntimeError(
                "INTERNAL: Mosaic failed to compile TPU kernel: boom"
            )

        integ._jit_cache = (integ._jit_cache[0], refuses)
        with pytest.raises(ChunkCompileError, match="Mosaic failed to compile"):
            integ.render(scene)
        assert len(calls) == 1, "a deterministic build failure was retried"
        assert plan.jfn is not refuses  # the plan itself was never touched

    def test_mesh_wider_than_the_machine_is_an_error(self):
        from tpu_pbrt.parallel.mesh import resolve_mesh
        from tpu_pbrt.utils.error import PbrtError

        with pytest.raises(PbrtError, match="needs 64 devices"):
            resolve_mesh((64,))
        assert resolve_mesh((4,)).devices.size == 4
        assert resolve_mesh(None) is None
