"""`correct` for `killeroo-manylight-frames-1chip` at its `test` preset (32x32,
64 spp, every pixel compared, limits of that size's own; a sphere of 288
triangles and 32 fixtures of 8 emissive triangles: 256 light rows, above the
dense select's 16, under the strategy a file gets by naming none): true for
the program as it is, false for both bfloat16 controls and for each fault.

The configuration's own fault: every light's pick pmf doubled where the
light is PICKED, without the pdf that weighs a BSDF-sampled hit on an
emitter following it. The light-sampled half of the direct light is then
divided by twice its pdf and its MIS weight moves the other way by less, so
the film darkens: the two halves no longer add up to the light, and the
comparison with a reference that samples lights alone must see it. Three
more are planted in the timed path as `test_correct.py` plants them (a
dispatch that returns its state unchanged; half of the samples left out;
radiance altered where it is deposited, by +10 %).

Run by hand, like its siblings: python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import pytest

import run as harness

CELL = "killeroo-manylight-frames-1chip"


def run_cell(seed=5):
    code, result = harness.run_cell(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "0", "--preset", "test"]
    )
    assert code == 3  # a preset run never prints a result
    return result


def failed_numbers(result):
    return sorted(k for k, row in result["compared"].items()
                  if row["value"] is None or row["value"] > row["limit"])


def test_sound_run_is_correct():
    result = run_cell()
    assert result["correct"], result["compared"]
    assert result["attempted"] == 1 and result["failed"] == 0


def test_sound_run_picks_from_the_spatial_table_and_counts_it():
    from tpu_pbrt.obs.trace import TRACE

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx, driver, config = harness.make_ctx(bench, CELL, 5, 0.0, False, "test")
    driver.setup(ctx)
    driver.window(ctx)
    driver.release(ctx)
    args = TRACE.spans("scene/light_distribution")[-1].args
    assert args["strategy_asked"] == args["strategy_built"] == "spatial" and args["light_rows"] == 256
    stats = ctx["frames"][0]["stats"]
    assert stats["regen"] and "mean_wave_occupancy" in stats
    read = lambda name: harness.load_module("metrics", name).read(ctx)  # noqa: E731
    assert 30.0 <= read("light_reads_per_pick") <= 37.0  # 8 steps + 15 a pick, 7 a valid vertex
    assert 0.3 < read("light_picks_per_ray") < 1.0
    assert read("light_distribution_s") > 0.0 and read("light_table_mb") > 0.5


def test_fault_pick_pmf_doubled_without_the_pdf_following(monkeypatch):
    from tpu_pbrt.core import lights_dev as ld

    search = ld.SpatialLightDistribution._search

    def doubled(self, u, voxel):
        idx, pmf = search(self, u, voxel)
        return idx, 2.0 * pmf

    monkeypatch.setattr(ld.SpatialLightDistribution, "_search", doubled)
    result = run_cell()
    assert not result["correct"]
    assert set(failed_numbers(result)) & {"mean_gap", "tile_gap"}


@pytest.mark.parametrize("lower", ["dtype", "intersect_dtype"])
def test_control_bfloat16_is_not_correct(lower):
    import jax.numpy as jnp

    compare = harness.load_module("", "compare")
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx, _, config = harness.make_ctx(bench, CELL, 5, 0.0, False, "test")
    ctx["desc"] = ctx["scene_writer"].build(config, ctx["seed"])
    limits = {k: config["check"]["limits"][k] for k in ("mean_gap", "tile_gap")}
    pix, ref_px = harness.reference_pixels(ctx, config)
    _, ctl_px = harness.reference_pixels(ctx, config, key_offset=1, **{lower: jnp.bfloat16})
    ok, rows = compare.verdict(harness.film_gaps(config, pix, ctl_px, ref_px), limits)
    assert not ok, rows
    # and float32 with other random numbers passes the same limits
    _, ref2 = harness.reference_pixels(ctx, config, key_offset=2)
    ok, rows = compare.verdict(harness.film_gaps(config, pix, ref2, ref_px), limits)
    assert ok, rows


def test_fault_state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.integrators.common import ChunkPlan

    orig = ChunkPlan.dispatch

    def dispatch(self, state, c):
        if c != self.n_chunks - 1:
            return orig(self, state, c)
        kept = jax.tree.map(jnp.copy, state)  # the argument is donated
        _, aux = orig(self, state, c)
        return kept, aux

    monkeypatch.setattr(ChunkPlan, "dispatch", dispatch)
    result = run_cell()
    assert not result["correct"]
    assert "spp_gap" in failed_numbers(result)


def test_fault_half_of_the_batch_left_out(monkeypatch):
    from tpu_pbrt.core.film import Film

    orig = Film.add_samples_pixel

    def add_samples_pixel(self, state, px, py, L, mask, ray_weight=None):
        import jax.numpy as jnp

        return orig(self, state, px, py, L, mask & (jnp.arange(px.shape[0]) % 2 == 0), ray_weight)

    monkeypatch.setattr(Film, "add_samples_pixel", add_samples_pixel)
    result = run_cell()
    assert not result["correct"]
    assert "spp_gap" in failed_numbers(result)


def test_fault_radiance_altered_where_deposited(monkeypatch):
    from tpu_pbrt.core.film import Film

    orig = Film.add_samples_pixel
    monkeypatch.setattr(
        Film, "add_samples_pixel",
        lambda self, state, px, py, L, mask, ray_weight=None: orig(
            self, state, px, py, L * 1.10, mask, ray_weight),
    )
    result = run_cell()
    assert not result["correct"]
    assert set(failed_numbers(result)) & {"mean_gap", "tile_gap"}
