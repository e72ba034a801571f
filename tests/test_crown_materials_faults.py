"""crown-materials: three faults planted in the program, each
outside the `test` preset's limits against the configuration's own plain
reference: the environment map's pdf without its 1/sin(theta), the map
read with phi mirrored, the conductor Fresnel term with eta and k swapped.
Each renders the preset anew through the `frames` driver with the fault
monkeypatched in (tests/test_crown_materials_config.py holds the sound
frame within the limits).
"""

import pytest

from tests.test_crown_materials_config import CELL, SEED, _frame, _verdict, harness  # noqa: F401  (harness: a fixture)


@pytest.fixture(scope="module")
def sound(harness):  # noqa: F811
    """The plain reference on the seed's pixels (the film it is compared with
    is the faulted one)."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx, _, config = harness.make_ctx(bench, CELL, SEED, 0.0, False, "test")
    ctx["desc"] = ctx["scene_writer"].build(config, SEED)
    pix, ref_px = harness.reference_pixels(ctx, config)
    return {"config": config, "pix": pix, "ref": ref_px}


def _no_sin_theta(mp):
    """The env pdf without its 1/sin(theta), where a light sample and an
    escape's MIS weight read it."""
    import jax.numpy as jnp

    from tpu_pbrt.core import lights_dev as ld
    from tpu_pbrt.core.vecmath import linear3, normalize

    env_pdf, env_sample = ld.env_pdf, ld._env_sample

    def sin_theta(dev, w):
        return jnp.sqrt(jnp.maximum(1.0 - normalize(linear3(dev["env_w2l"], w))[..., 2] ** 2, 0.0))

    def sample(dev, u1, u2):
        wi, pdf, li = env_sample(dev, u1, u2)
        return wi, pdf * sin_theta(dev, wi), li

    mp.setattr(ld, "env_pdf", lambda dev, d: env_pdf(dev, d) * sin_theta(dev, d))
    mp.setattr(ld, "_env_sample", sample)


def _phi_mirrored(mp):
    """The map read at phi -> 2 pi - phi (light-space y negated)."""
    import jax.numpy as jnp

    from tpu_pbrt.core import lights_dev as ld
    from tpu_pbrt.core.vecmath import linear3

    lookup = ld.env_lookup

    def mirrored(dev, d):
        flip = dev["env_w2l"].T @ jnp.diag(jnp.asarray([1.0, -1.0, 1.0])) @ dev["env_w2l"]
        return lookup(dev, linear3(flip, d))

    mp.setattr(ld, "env_lookup", mirrored)


def _fresnel_swapped(mp):
    """FrConductor with eta and k swapped."""
    from tpu_pbrt.core import bxdf

    fresnel = bxdf.fresnel_conductor
    mp.setattr(bxdf, "fresnel_conductor", lambda cos_i, eta, k: fresnel(cos_i, k, eta))


@pytest.mark.parametrize("plant", [_no_sin_theta, _phi_mirrored, _fresnel_swapped], ids=lambda f: f.__name__[1:])
def test_a_planted_fault_is_outside_the_limits(harness, sound, plant):  # noqa: F811
    with pytest.MonkeyPatch.context() as mp:
        plant(mp)
        _, _, image, weight, _ = _frame(harness)
    correct, rows = _verdict(harness, sound, image, weight)
    over = {k for k, row in rows.items() if row["value"] > row["limit"]}
    assert not correct and over & {"mean_gap", "tile_gap"} and not over & {"spp_gap", "nonfinite"}, rows
