"""Checkpoint/resume bit-compatibility (SURVEY.md §5.4) and the stats
registry report format (§5.1/§5.5)."""

import numpy as np
import pytest

from tpu_pbrt.parallel.checkpoint import load_checkpoint, save_checkpoint
from tpu_pbrt.scenes import compile_api, make_cornell
from tpu_pbrt.utils.stats import STATS, ProgressReporter, StatsRegistry


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        api = make_cornell(res=16, spp=2, integrator="directlighting", maxdepth=1)
        scene, integ = compile_api(api)
        st = scene.film.init_state()
        p = str(tmp_path / "ck.npz")
        save_checkpoint(p, st, 7, 1234)
        st2, nxt, rays, ctr = load_checkpoint(p)
        assert nxt == 7 and rays == 1234 and ctr == {}
        assert np.array_equal(np.asarray(st.rgb), np.asarray(st2.rgb))

    def test_resume_bit_identical(self, tmp_path):
        """A render interrupted at a checkpoint and resumed produces the
        same image as an uninterrupted one (counter-based RNG + idempotent
        chunks)."""
        import os

        os.environ["TPU_PBRT_CHUNK"] = "1024"  # force multiple chunks
        from tpu_pbrt import config

        config.reload()
        try:
            api = make_cornell(res=16, spp=8, integrator="directlighting", maxdepth=2)
            scene, integ = compile_api(api)
            full = integ.render(scene)

            # simulate interruption: checkpoint after every chunk, then
            # resume from the halfway checkpoint
            p = str(tmp_path / "resume.npz")
            api2 = make_cornell(res=16, spp=8, integrator="directlighting", maxdepth=2)
            scene2, integ2 = compile_api(api2)
            integ2.render(scene2, checkpoint_path=p, checkpoint_every=1)
            st, nxt, rays, _ = load_checkpoint(p)
            # rewind the cursor to mid-render and resume
            save_checkpoint(p, scene2.film.init_state(), 0, 0)
            r3 = integ2.render(scene2, checkpoint_path=p, checkpoint_every=1)
            assert np.allclose(full.image, r3.image, atol=1e-6)
        finally:
            del os.environ["TPU_PBRT_CHUNK"]


class TestStats:
    def test_report_format(self):
        reg = StatsRegistry()
        reg.counter("Integrator/Camera rays traced", 100)
        reg.memory_counter("Scene/BVH memory", 3 << 20)
        reg.percent("Intersections/Regular ray intersection tests", 40, 100)
        reg.ratio("Scene/Rays per sample", 30, 10)
        reg.distribution("Integrator/Path length", 3)
        reg.distribution("Integrator/Path length", 5)
        with reg.phase("Accelerator/Intersect"):
            pass
        text = reg.report()
        assert "Statistics:" in text
        assert "Camera rays traced" in text
        assert "3.00 MiB" in text
        assert "(40.00%)" in text
        assert "(3.00x)" in text
        assert "4.000 avg" in text
        assert "Accelerator/Intersect" in text

    def test_global_registry_counts(self):
        STATS.counter("Test/widget", 2)
        STATS.counter("Test/widget", 3)
        assert STATS.counters["Test/widget"] >= 5

    def test_progress_quiet(self):
        p = ProgressReporter(10, "t", quiet=True)
        for _ in range(10):
            p.update()
        p.done()


class TestCheckpointFingerprint:
    def test_mismatched_config_rejected(self, tmp_path):
        """A checkpoint written under one (chunk, spp, scene) configuration
        must refuse to resume under another instead of silently corrupting
        the image (ADVICE r1)."""
        import jax.numpy as jnp
        import pytest

        from tpu_pbrt.core.film import FilmState

        st = FilmState(
            rgb=jnp.zeros((4, 4, 3)), weight=jnp.zeros((4, 4)), splat=jnp.zeros((4, 4, 3))
        )
        p = str(tmp_path / "ck.npz")
        save_checkpoint(p, st, 3, 100, fingerprint="chunk=1024;spp=8")
        # same fingerprint resumes
        _, nxt, rays, _ = load_checkpoint(p, "chunk=1024;spp=8")
        assert (nxt, rays) == (3, 100)
        # different fingerprint is refused
        with pytest.raises(ValueError, match="different render configuration"):
            load_checkpoint(p, "chunk=2048;spp=8")


class TestCheckpointCounters:
    """ISSUE 4 satellite: the cumulative telemetry-counter snapshot is a
    versioned checkpoint field, so a resumed render reports end-to-end
    totals."""

    def _tiny_state(self):
        import jax.numpy as jnp

        from tpu_pbrt.core.film import FilmState

        return FilmState(
            rgb=jnp.zeros((4, 4, 3)), weight=jnp.zeros((4, 4)),
            splat=jnp.zeros((4, 4, 3)),
        )

    @pytest.mark.parametrize(
        "extra", [
            {"brute_rays": 4912, "stream_leaf_tests": 700, "stream_block_slots": 1024,
             "stream_pairs_deferred": 9},
            {"brute_rays": 4912, "stream_leaf_tests": 700, "stream_block_slots": 1024},
            {"brute_rays": 4912, "stream_leaf_tests": 700}, {}, {"lanes_compacted": 4518},
        ],
        ids=["today", "written_before_pr36", "written_before_pr32", "written_before_pr27",
             "written_before_pr26"])
    def test_counter_snapshot_roundtrip(self, tmp_path, extra):
        """The snapshot is a dict by name: one written while the pool
        still compacted (`lanes_compacted`, gone with ISSUE 26), or
        before the brute tracer counted its rays (`brute_rays`, ISSUE
        27), or before the flush counted its block slots
        (`stream_block_slots`, ISSUE 32), or before EXPAND counted the
        pairs it put back (`stream_pairs_deferred`, ISSUE 36), loads and
        merges with today's counter block without a fault."""
        from tpu_pbrt.obs import counters as obs_counters

        snap = {
            "rays_traced": 4912, "lanes_regenerated": 1024,
            "occupancy_histogram": [0, 1, 2, 3, 0, 0, 0, 4], **extra,
        }
        p = str(tmp_path / "ck.npz")
        save_checkpoint(p, self._tiny_state(), 2, 99, counters=snap)
        _, nxt, rays, ctr = load_checkpoint(p)
        assert (nxt, rays) == (2, 99)
        assert ctr == snap
        assert "lanes_compacted" not in obs_counters.HOST_FIELDS
        drain = obs_counters.zeros()._replace(
            rays=4, st_leaf=100, st_slots=256, st_def=3)
        now = obs_counters.to_host([drain, drain])  # summed over a frame's drains
        merged = obs_counters.merge_host(ctr, now)
        assert merged["stream_block_slots"] == extra.get("stream_block_slots", 0) + 512
        assert merged["stream_leaf_tests"] == extra.get("stream_leaf_tests", 0) + 200
        assert merged["stream_pairs_deferred"] == extra.get("stream_pairs_deferred", 0) + 6
        # a scene no stream tracer runs carries nothing for the slots,
        # nor for the pairs put back
        brute = obs_counters.to_host([obs_counters.zeros(stream=False)])
        assert "stream_block_slots" not in brute and brute["stream_leaf_tests"] == 0
        assert "stream_pairs_deferred" not in brute
        assert merged["rays_traced"] == 4920
        assert merged["lanes_regenerated"] == 1024
        assert merged.get("lanes_compacted") == extra.get("lanes_compacted")
        assert merged["brute_rays"] == extra.get("brute_rays", 0)
        assert obs_counters.with_brute_pairs(merged, 36)["brute_pairs_tested"] == 36 * merged["brute_rays"]
        # a snapshot that nothing of this process was merged into keeps its keys
        assert obs_counters.with_brute_pairs(ctr, 36).get("brute_pairs_tested") == (
            36 * 4912 if "brute_rays" in extra else None)

    def test_v2_checkpoint_loads_without_counters(self, tmp_path):
        """A pre-telemetry (v2) file — no counters field — still resumes,
        with an empty snapshot."""
        st = self._tiny_state()
        p = str(tmp_path / "old.npz")
        np.savez_compressed(
            p, version=2, rgb=np.asarray(st.rgb),
            weight=np.asarray(st.weight), splat=np.asarray(st.splat),
            next_chunk=5, rays=777, fingerprint=np.array(""),
        )
        st2, nxt, rays, ctr = load_checkpoint(p)
        assert (nxt, rays, ctr) == (5, 777, {})

    def test_resumed_render_reports_end_to_end_totals(self, tmp_path):
        """Resume a FINISHED pool render from its checkpoint: zero new
        chunks run, yet the reported telemetry counters are the full
        render's totals (seeded from the snapshot)."""
        import os

        from tpu_pbrt.scenes import compile_api, make_cornell

        os.environ["TPU_PBRT_CHUNK"] = "1024"  # force multiple chunks
        from tpu_pbrt import config

        config.reload()
        try:
            api = make_cornell(res=16, spp=8, integrator="path", maxdepth=2)
            scene, integ = compile_api(api)
            p = str(tmp_path / "pool.npz")
            full = integ.render(scene, checkpoint_path=p, checkpoint_every=1)
            totals = full.stats["telemetry"]["counters"]
            assert totals["rays_traced"] == full.rays_traced > 0
            resumed = integ.render(
                scene, checkpoint_path=p, checkpoint_every=1
            )
            assert resumed.stats["telemetry"]["counters"] == totals
            # a telemetry-OFF resume must not report the saved snapshot
            # as this render's totals (it covers none of this process's
            # work) — but the checkpoint keeps carrying it forward so a
            # later telemetry-on resume still reports true totals
            os.environ["TPU_PBRT_TELEMETRY"] = "0"
            config.reload()
            off = integ.render(scene, checkpoint_path=p, checkpoint_every=1)
            assert "telemetry" not in off.stats
            _, _, _, ctr = load_checkpoint(p)
            # the pairs are derived from `brute_rays` where the stats are
            # made (rays x the scene's triangles), not stored
            pairs = totals.pop("brute_pairs_tested")
            assert pairs == scene.n_tris * totals["brute_rays"] > 0
            assert ctr == totals
        finally:
            del os.environ["TPU_PBRT_CHUNK"]
            os.environ.pop("TPU_PBRT_TELEMETRY", None)
