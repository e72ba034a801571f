"""Interpolation machinery tests (interpolation.cpp capability): spline
reproduction, spline importance sampling, Fourier recurrence, and the
curve shape's ribbon tessellation."""

import numpy as np
import jax.numpy as jnp

from tpu_pbrt.core.interpolation import (
    catmull_rom,
    find_interval,
    fourier,
    integrate_catmull_rom,
    sample_catmull_rom,
)


def test_find_interval():
    xs = jnp.asarray([0.0, 1.0, 2.0, 5.0, 9.0])
    x = jnp.asarray([-1.0, 0.0, 0.5, 1.0, 4.9, 9.0, 20.0])
    out = np.asarray(find_interval(xs, x))
    np.testing.assert_array_equal(out, [0, 0, 0, 1, 2, 3, 3])


def test_catmull_rom_interpolates_nodes_and_smooth():
    xs = np.linspace(0.0, 1.0, 9)
    fs = np.sin(2 * np.pi * xs) + 2.0
    out = np.asarray(catmull_rom(jnp.asarray(xs), jnp.asarray(fs), jnp.asarray(xs)))
    np.testing.assert_allclose(out, fs, atol=1e-5)
    # between nodes the spline tracks the smooth function closely
    xq = np.linspace(0.05, 0.95, 50)
    out = np.asarray(catmull_rom(jnp.asarray(xs), jnp.asarray(fs), jnp.asarray(xq)))
    np.testing.assert_allclose(out, np.sin(2 * np.pi * xq) + 2.0, atol=0.03)


def test_sample_catmull_rom_matches_density():
    """Samples drawn via SampleCatmullRom must be distributed like the
    spline: compare a histogram to the normalized function."""
    xs = np.linspace(0.0, 1.0, 17)
    fs = 0.2 + (xs - 0.3) ** 2  # positive, non-uniform
    cdf, total = integrate_catmull_rom(xs, fs)
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.uniform(size=200_000), jnp.float32)
    x, fval, pdf = sample_catmull_rom(xs, fs, cdf, u)
    x = np.asarray(x)
    assert (x >= 0).all() and (x <= 1).all()
    hist, edges = np.histogram(x, bins=16, range=(0, 1), density=True)
    centers = 0.5 * (edges[:-1] + edges[1:])
    expect = (0.2 + (centers - 0.3) ** 2) / total
    np.testing.assert_allclose(hist, expect, rtol=0.08)
    # importance-sampling identity: E[f(x)/pdf(x)] = integral of f = total
    est = np.mean((0.2 + (x - 0.3) ** 2) / np.maximum(np.asarray(pdf), 1e-9))
    np.testing.assert_allclose(est, total, rtol=0.05)


def test_fourier_matches_direct_sum():
    rng = np.random.default_rng(7)
    m = 12
    a = jnp.asarray(rng.normal(size=(64, m)), jnp.float32)
    phi = rng.uniform(0, 2 * np.pi, 64)
    out = np.asarray(fourier(a, jnp.asarray(np.cos(phi), jnp.float32), m))
    direct = np.sum(
        np.asarray(a) * np.cos(np.arange(m)[None, :] * phi[:, None]), axis=1
    )
    np.testing.assert_allclose(out, direct, atol=1e-3)


def test_curve_shape_tessellates_and_renders():
    from tests.test_render import MATTE_DEPTH1, render_scene, scene_header

    r = render_scene(
        scene_header("directlighting", spp=4, res=24, extra=MATTE_DEPTH1)
        + '''
WorldBegin
LightSource "distant" "rgb L" [5 5 5] "point from" [0 0 -1] "point to" [0 0 0]
Material "matte" "rgb Kd" [0.8 0.8 0.8]
Shape "curve" "point P" [-1 0 0  -0.3 0.8 0  0.3 -0.8 0  1 0 0] "float width" [0.4]
WorldEnd
'''
    )
    img = r.image
    assert np.isfinite(img).all()
    assert img.mean() > 1e-3, "curve ribbon rendered black"
