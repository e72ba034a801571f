"""Sampler plugin tests (VERDICT r3 #7): the scene file's Sampler
directive must select a real stream structure, and the low-discrepancy
samplers must beat the random sampler at equal spp."""

import numpy as np
import jax.numpy as jnp

from tpu_pbrt.core.sampling import (
    PRIMES,
    normalize_sampler_name,
    radical_inverse_prime,
    sample_1d,
    sample_2d,
)


def test_radical_inverse_base3_values():
    n = jnp.asarray([0, 1, 2, 3, 4, 9], jnp.uint32)
    out = np.asarray(radical_inverse_prime(3, n))
    np.testing.assert_allclose(
        out, [0.0, 1 / 3, 2 / 3, 1 / 9, 1 / 9 + 1 / 3, 1 / 27], atol=1e-6
    )


def test_scrambled_radical_inverse_is_permutation():
    """The digit scramble must keep the first b^2 points distinct and
    stratified (a permutation of the base-b digit grid)."""
    for base in (3, 5):
        n = jnp.arange(base * base, dtype=jnp.uint32)
        out = np.asarray(radical_inverse_prime(base, n, scramble_seed=12345))
        # all distinct
        assert len(np.unique(np.round(out * base * base).astype(int))) == base * base
        # one point in each of the b^2 strata
        strata = np.floor(out * base * base).astype(int)
        assert sorted(strata) == list(range(base * base))


def _mean_rms(kind, spp, n_pix=256, dim=11):
    px = jnp.arange(n_pix, dtype=jnp.int32) % 16
    py = jnp.arange(n_pix, dtype=jnp.int32) // 16
    acc = np.zeros(n_pix)
    for s in range(spp):
        u = sample_1d(kind, spp, px, py, jnp.full((n_pix,), s, jnp.int32), dim)
        acc += np.asarray(u)
    return float(np.sqrt(np.mean((acc / spp - 0.5) ** 2)))


def test_ld_beats_random_1d():
    spp = 16
    r = _mean_rms("random", spp)
    for kind in ("02", "halton", "stratified"):
        ld = _mean_rms(kind, spp)
        assert ld < 0.5 * r, f"{kind}: rms {ld} not < half of random {r}"


def _prod_rms(kind, spp, n_pix=256, dim=5):
    """2D integration of f(u,v) = u*v (true mean 1/4) per pixel."""
    px = jnp.arange(n_pix, dtype=jnp.int32) % 16
    py = jnp.arange(n_pix, dtype=jnp.int32) // 16
    acc = np.zeros(n_pix)
    for s in range(spp):
        u, v = sample_2d(kind, spp, px, py, jnp.full((n_pix,), s, jnp.int32), dim)
        acc += np.asarray(u * v)
    return float(np.sqrt(np.mean((acc / spp - 0.25) ** 2)))


def test_ld_beats_random_2d():
    spp = 16
    r = _prod_rms("random", spp)
    # (0,2) is base-2 through and through: near-perfect at power-of-two
    # spp. Halton's odd-prime pairs only fully stratify at b^k samples,
    # so its margin at spp=16 is real but smaller (pbrt's Halton has the
    # same property).
    for kind, bound in (("02", 0.6), ("halton", 0.8)):
        ld = _prod_rms(kind, spp)
        assert ld < bound * r, f"{kind}: rms {ld} not < {bound}x random {r}"


def test_dimension_decorrelation():
    """Two different dimensions of the same sampler must not be linearly
    correlated across the sample index (the classic radical-inverse
    pitfall this dispatch's shuffling/scrambling exists to prevent)."""
    spp = 64
    px = jnp.zeros((1,), jnp.int32)
    py = jnp.zeros((1,), jnp.int32)
    for kind in ("02", "halton"):
        for d1, d2 in ((5, 21), (4, 8), (7, 23)):
            a = np.array(
                [
                    float(sample_1d(kind, spp, px, py, jnp.full((1,), s, jnp.int32), d1)[0])
                    for s in range(spp)
                ]
            )
            b = np.array(
                [
                    float(sample_1d(kind, spp, px, py, jnp.full((1,), s, jnp.int32), d2)[0])
                    for s in range(spp)
                ]
            )
            c = abs(np.corrcoef(a, b)[0, 1])
            assert c < 0.5, f"{kind} dims {d1},{d2} correlated: {c:.2f}"


def test_sampler_name_dispatch():
    assert normalize_sampler_name("sobol") == "sobol"
    assert normalize_sampler_name("zerotwosequence") == "02"
    assert normalize_sampler_name("maxmindist") == "02"  # loud substitute
    assert normalize_sampler_name("halton") == "halton"
    assert normalize_sampler_name("random") == "random"
    assert normalize_sampler_name("stratified") == "stratified"


def test_render_honors_sampler_name():
    """Same scene, different Sampler directives -> different images with
    ~equal means (the estimator is unbiased under every sampler), and the
    LD render is closer to a high-spp reference than the random one."""
    from tests.test_render import MATTE_DEPTH1, QUAD, render_scene

    def scene(sampler, spp):
        return f'''
Integrator "directlighting" {MATTE_DEPTH1}
Sampler "{sampler}" "integer pixelsamples" [{spp}]
PixelFilter "box"
Film "image" "integer xresolution" [16] "integer yresolution" [16] "string filename" [""]
LookAt 0 0 -3  0 0 0  0 1 0
Camera "perspective" "float fov" [60]
WorldBegin
AttributeBegin
AreaLightSource "diffuse" "rgb L" [8 8 8]
Shape "trianglemesh" {QUAD} "point P" [-0.4 0.95 -0.4  0.4 0.95 -0.4  0.4 0.95 0.4  -0.4 0.95 0.4]
AttributeEnd
Material "matte" "rgb Kd" [0.6 0.6 0.6]
Shape "trianglemesh" {QUAD} "point P" [-2 -1 2   2 -1 2   2 -1 -2  -2 -1 -2]
WorldEnd
'''

    ref = render_scene(scene("sobol", 128)).image
    img_r = render_scene(scene("random", 8)).image
    img_s = render_scene(scene("sobol", 8)).image
    assert not np.allclose(img_r, img_s), "sampler name ignored"
    mse_r = float(np.mean((img_r - ref) ** 2))
    mse_s = float(np.mean((img_s - ref) ** 2))
    assert mse_s < mse_r, f"sobol mse {mse_s} not below random {mse_r}"
    # unbiasedness: means agree within noise
    assert abs(img_r.mean() - img_s.mean()) / ref.mean() < 0.15
