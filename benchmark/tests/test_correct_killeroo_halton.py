"""`correct` for `killeroo-halton-frames-1chip` at its `test` preset (32x32,
64 spp under the halton sampler, every pixel compared, limits of that size's
own; the 2,212-triangle displaced sphere through PLY): true for the program
as it is, which renders it through the pool wavefront, false for both
bfloat16 controls and for each fault a frame can have.

Three faults are planted in the timed path itself, as `test_correct.py`
plants them (a dispatch that returns its state unchanged; half of the
samples left out, the mean taken over the rest; radiance altered where it is
deposited, by +10 %). The fourth, the chips' exchange left out, has no place
in a one-chip cell's path: it is planted in the film the run produced, as
`control.py --faults` plants it (one chip's quarter of the samples). A fifth
is this configuration's own: every lane of the pool given lane 0's pair of
prime bases. Its film is another estimate of the same light, so the
comparison with the reference cannot see it, and that is said here rather
than hoped: the fixed-batch loop's film can (tests/test_halton_reference.py).

Run by hand, like its siblings: python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import pytest

import run as harness

CELL = "killeroo-halton-frames-1chip"


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


def test_sound_run_goes_through_the_pool_and_reports_its_pairs():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx, driver, config = harness.make_ctx(bench, CELL, 5, 0.0, False, "test")
    assert config["sampler"] == "halton"
    driver.setup(ctx)
    driver.window(ctx)
    driver.release(ctx)
    stats = ctx["frames"][0]["stats"]
    assert stats["regen"] and "mean_wave_occupancy" in stats
    per_ray = harness.load_module("metrics", "halton_pairs_per_ray").read(ctx)
    assert 1.0 < per_ray <= 2.0  # two pairs a live lane a wave, one or two rays


def test_one_pair_for_all_lanes_is_still_an_estimate_of_the_same_light(monkeypatch):
    """The mutation tests/test_halton_reference.py fails by the fixed-batch
    loop's film: another choice of bases is other samples of the same
    integrand, so the film stays inside the limits here."""
    import jax.numpy as jnp

    from tpu_pbrt.core import sampling

    which = sampling._halton_which

    def lane_zeros(salt):
        w = which(salt)
        return jnp.broadcast_to(w.reshape(-1)[0], w.shape) if getattr(w, "ndim", 0) else w

    monkeypatch.setattr(sampling, "_halton_which", lane_zeros)
    result = run_cell()
    assert result["correct"], result["compared"]


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


def test_fault_exchange_left_out_of_the_film():
    compare = harness.load_module("", "compare")
    control = harness.load_module("", "control")
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx, driver, config = harness.make_ctx(bench, CELL, 5, 0.0, False, "test")
    driver.setup(ctx)
    driver.window(ctx)
    image, weight = driver.film(ctx)
    driver.release(ctx)
    spp = int(config["pixelsamples"])
    assert compare.verdict(compare.film_numbers(image, weight, spp),
                           {"spp_gap": 0.0, "nonfinite": 0.0})[0]
    image, weight = control.plant("no_exchange", image, weight)
    ok, rows = compare.verdict(compare.film_numbers(image, weight, spp), {"spp_gap": 0.0, "nonfinite": 0.0})
    assert not ok and rows["spp_gap"]["value"] == 0.75 * spp
