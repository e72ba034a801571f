"""`correct` comes out true for the program as it is and false for the
control and for each fault a frame can have, at the configurations' `test`
preset (32x32, 64 spp, every pixel compared, limits of that size's own).

The controls are the plain reference computed in bfloat16, and the reference
with the ray-triangle test alone in bfloat16, each put in the program's place. The faults are planted in the timed path itself, under the
harness: a dispatch that returns its state unchanged; half of the samples
left out of the film, the mean taken over the rest; the film's exchange
between chips left out; radiance altered where it is deposited."""

import pytest

import run as harness

ONE_CHIP = ["killeroo-frames-1chip"]
MESH = "killeroo-frames-mesh4"


def run_cell(workload, seed=5):
    code, result = harness.run_cell(
        ["--workload", workload, "--seed", str(seed), "--seconds", "0", "--preset", "test"]
    )
    assert code == 3  # a preset run never prints a result
    return result


def failed_numbers(result):
    return sorted(k for k, row in result["compared"].items()
                  if row["value"] is None or row["value"] > row["limit"])


@pytest.mark.parametrize("workload", ONE_CHIP + [MESH])
def test_sound_run_is_correct(workload):
    result = run_cell(workload)
    assert result["correct"], result["compared"]
    assert result["attempted"] == 1 and result["failed"] == 0


@pytest.mark.parametrize("lower", ["dtype", "intersect_dtype"])
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_bfloat16_is_not_correct(workload, lower):
    import jax.numpy as jnp

    compare = harness.load_module("", "compare")
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx, driver, config = harness.make_ctx(bench, workload, 5, 0.0, False, "test")
    ctx["desc"] = ctx["scene_writer"].build(config, ctx["seed"])
    pix, ref_px = harness.reference_pixels(ctx, config)
    _, ctl_px = harness.reference_pixels(ctx, config, key_offset=1, **{lower: jnp.bfloat16})
    ok, rows = compare.verdict(harness.film_gaps(config, pix, ctl_px, ref_px),
                               {k: config["check"]["limits"][k] for k in ("mean_gap", "tile_gap")})
    assert not ok, rows
    # and float32 with other random numbers passes the same limits
    _, ref2 = harness.reference_pixels(ctx, config, key_offset=2)
    ok, rows = compare.verdict(harness.film_gaps(config, pix, ref2, ref_px),
                               {k: config["check"]["limits"][k] for k in ("mean_gap", "tile_gap")})
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
    result = run_cell(ONE_CHIP[0])
    assert not result["correct"]
    assert "spp_gap" in failed_numbers(result)


def test_fault_half_of_the_batch_left_out(monkeypatch):
    from tpu_pbrt.core.film import Film

    orig = Film.add_samples_pixel

    def add_samples_pixel(self, state, px, py, L, mask, ray_weight=None):
        # every second lane's sample never reaches the film, and the film's
        # own weights take the mean over the rest
        import jax.numpy as jnp

        return orig(self, state, px, py, L, mask & (jnp.arange(px.shape[0]) % 2 == 0), ray_weight)

    monkeypatch.setattr(Film, "add_samples_pixel", add_samples_pixel)
    result = run_cell(ONE_CHIP[0])
    assert not result["correct"]
    assert "spp_gap" in failed_numbers(result)


def test_fault_radiance_altered_where_deposited(monkeypatch):
    from tpu_pbrt.core.film import Film

    orig = Film.add_samples_pixel
    monkeypatch.setattr(
        Film, "add_samples_pixel",
        lambda self, state, px, py, L, mask, ray_weight=None: orig(
            self, state, px, py, L * 1.15, mask, ray_weight),
    )
    result = run_cell(ONE_CHIP[0])
    assert not result["correct"]
    assert set(failed_numbers(result)) & {"mean_gap", "tile_gap"}


def test_fault_exchange_between_chips_left_out(monkeypatch):
    import jax
    import jax.numpy as jnp

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices: run through benchmark/tests/conftest.py")
    orig = jax.lax.psum

    def only_the_first_chips_share(x, axis_name, **kw):
        mine = jax.lax.axis_index(axis_name) == 0
        return orig(jax.tree.map(lambda a: jnp.where(mine, a, jnp.zeros_like(a)), x), axis_name, **kw)

    monkeypatch.setattr(jax.lax, "psum", only_the_first_chips_share)
    result = run_cell(MESH)
    assert not result["correct"]
    assert "spp_gap" in failed_numbers(result)
