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


def test_sharded_render_matches_single_device(monkeypatch):
    from tpu_pbrt import config

    # two chunks, so that the second dispatch sees the first one's output
    monkeypatch.setenv("TPU_PBRT_CHUNK", str(24 * 24 * 4))
    config.reload()
    api = make_cornell(res=24, spp=8, integrator="path", maxdepth=3)
    scene, integ = compile_api(api)
    r_single = integ.render(scene)

    api2 = make_cornell(res=24, spp=8, integrator="path", maxdepth=3)
    scene2, integ2 = compile_api(api2)
    r_mesh = integ2.render(scene2, mesh=make_mesh(8))
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
