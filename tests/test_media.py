"""Participating-media tests: HG phase normalization/sampling consistency
(pbrt src/tests/hg.cpp counterpart) and analytic Beer-Lambert attenuation
through the volpath integrator."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_pbrt.core import media as md
from tpu_pbrt.core.sampling import uniform_float
from tests.test_render import render_scene


class TestHenyeyGreenstein:
    @pytest.mark.parametrize("g", [-0.6, -0.1, 0.0, 0.3, 0.9])
    def test_normalization(self, g):
        """Integral of p over the sphere = 1 (hg.cpp HenyeyGreenstein test)."""
        mu = np.linspace(-1, 1, 20001)
        p = np.asarray(md.hg_p(jnp.asarray(mu), g))
        integral = 2 * np.pi * np.trapezoid(p, mu)
        assert abs(integral - 1.0) < 1e-3, (g, integral)

    @pytest.mark.parametrize("g", [-0.5, 0.0, 0.7])
    def test_sampling_consistency(self, g):
        """Sampled directions reproduce the analytic mean cosine. pbrt's
        convention has wo pointing BACK along the incoming ray, so forward
        scattering is dot(wo,wi) = -1 and E[dot(wo,wi)] = -g."""
        n = 200_000
        i = jnp.arange(n)
        u1 = uniform_float(i, 101)
        u2 = uniform_float(i, 202)
        wo = jnp.broadcast_to(jnp.asarray([0.0, 0.0, 1.0]), (n, 3))
        wi, pdf = md.hg_sample(wo, jnp.full((n,), g, jnp.float32), u1, u2)
        wi = np.asarray(wi)
        assert np.allclose(np.linalg.norm(wi, axis=-1), 1.0, atol=1e-4)
        mu = wi[:, 2]  # dot(wo, wi)
        assert abs(mu.mean() - (-g)) < 5e-3, (g, mu.mean())
        # pdf returned must match hg_p at the sampled angle, and be a
        # correctly normalized density: E[1/(2 pi p)] = integral dmu = 2
        p2 = np.asarray(md.hg_p(jnp.asarray(mu), g))
        assert np.allclose(np.asarray(pdf), p2, rtol=1e-3, atol=1e-5)
        assert abs(float(np.mean(1.0 / (2 * np.pi * np.asarray(pdf)))) - 2.0) < 0.02


class TestVolPath:
    def test_beer_lambert_absorption(self):
        """Camera inside a purely absorbing homogeneous medium looking at an
        area light: pixel = Le * exp(-sigma_a * distance)."""
        sigma_a = 0.4
        dist = 3.0
        r = render_scene(
            f'''
Integrator "volpath" "integer maxdepth" [3]
Sampler "halton" "integer pixelsamples" [512]
PixelFilter "box"
Film "image" "integer xresolution" [16] "integer yresolution" [16] "string filename" [""]
LookAt 0 0 -3  0 0 0  0 1 0
MakeNamedMedium "fog" "string type" "homogeneous" "rgb sigma_a" [{sigma_a} {sigma_a} {sigma_a}] "rgb sigma_s" [0 0 0]
MediumInterface "" "fog"
Camera "perspective" "float fov" [50]
WorldBegin
AttributeBegin
  AreaLightSource "diffuse" "rgb L" [5 5 5]
  Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-4 -4 0  -4 4 0  4 4 0  4 -4 0]
AttributeEnd
WorldEnd
'''
        )
        img = r.image
        expected = 5.0 * np.exp(-sigma_a * dist)
        got = float(img[7:9, 7:9].mean())
        assert abs(got - expected) / expected < 0.05, (got, expected)

    def test_no_medium_matches_path(self):
        """volpath on a medium-free scene must agree with path."""
        body = '''
WorldBegin
AttributeBegin
  AreaLightSource "diffuse" "rgb L" [8 8 8]
  Translate 0 1.8 0
  Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-0.6 0 -0.6  0.6 0 -0.6  0.6 0 0.6  -0.6 0 0.6]
AttributeEnd
Material "matte" "rgb Kd" [0.7 0.6 0.5]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-2 -2 2  2 -2 2  2 2 2  -2 2 2]
WorldEnd
'''
        hdr = '''
Sampler "halton" "integer pixelsamples" [128]
PixelFilter "box"
Film "image" "integer xresolution" [20] "integer yresolution" [20] "string filename" [""]
LookAt 0 0 -3  0 0 0  0 1 0
Camera "perspective" "float fov" [60]
'''
        r1 = render_scene('Integrator "volpath" "integer maxdepth" [2]' + hdr + body)
        r2 = render_scene('Integrator "path" "integer maxdepth" [2]' + hdr + body)
        mse = float(np.mean((r1.image - r2.image) ** 2))
        scale = float(np.mean(r2.image**2)) + 1e-9
        assert mse / scale < 0.01, mse / scale

    def test_scattering_medium_brightens_shadow(self):
        """An isotropically scattering fog between light and a shadowed
        region adds in-scattered radiance where the direct path is blocked:
        single-scatter NEE from medium interactions must be nonzero."""
        r = render_scene(
            '''
Integrator "volpath" "integer maxdepth" [3]
Sampler "halton" "integer pixelsamples" [64]
PixelFilter "box"
Film "image" "integer xresolution" [16] "integer yresolution" [16] "string filename" [""]
LookAt 0 0 -3  0 0 0  0 1 0
MakeNamedMedium "fog" "string type" "homogeneous" "rgb sigma_a" [0.01 0.01 0.01] "rgb sigma_s" [0.4 0.4 0.4] "float g" [0.0]
MediumInterface "" "fog"
Camera "perspective" "float fov" [50]
WorldBegin
LightSource "point" "rgb I" [20 20 20] "point from" [0 2 0]
Material "matte" "rgb Kd" [0.1 0.1 0.1]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-9 -9 4  9 -9 4  9 9 4  -9 9 4]
WorldEnd
'''
        )
        img = r.image
        # fog glow: every pixel picks up in-scattered light
        assert float(img.min()) > 0.0
        assert float(img.mean()) > 0.01
