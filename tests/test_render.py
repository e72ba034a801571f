"""End-to-end render tests with closed-form oracles.

Mirrors pbrt-v3's src/tests/analytic_scenes.cpp strategy (SURVEY.md §4):
build tiny scenes through the scene-description API in-process, render with
several integrator combinations, and assert the result matches analytic
radiance within noise tolerance — an oracle without golden images. Also
cross-checks integrators against each other (path vs directlighting on
direct-only scenes), the upstream ecosystem's convergence test.
"""

import numpy as np
import pytest

from tpu_pbrt.scene.api import Options, parse_string, pbrt_init


def render_scene(text, quiet=True):
    api = pbrt_init(Options(quiet=quiet))
    parse_string(text, api, render=True)
    return api.result


def scene_header(integrator, spp=16, res=32, extra=""):
    return f'''
Integrator "{integrator}" {extra}
Sampler "halton" "integer pixelsamples" [{spp}]
PixelFilter "box"
Film "image" "integer xresolution" [{res}] "integer yresolution" [{res}] "string filename" [""]
LookAt 0 0 -3  0 0 0  0 1 0
Camera "perspective" "float fov" [60]
'''


QUAD = '"integer indices" [0 1 2 0 2 3]'

# `directlighting` unrolls one level of its program per `maxdepth` (default
# 5) and goes on past a hit along specular bounces only. In a scene with no
# specular material no lane is alive past the first hit, so depth 1 gives
# the default's image bit for bit from a fifth of the program: 66-134 s a
# case cold became 4-16 s (ISSUE 28). That the dead lanes of depths 1 to 4
# add nothing is itself held: the point, distant and shadow oracles of
# tests/test_render_lights.py render at the default depth, and one case
# there holds depth 1 to it bytewise. Do not take this in a scene that
# test does not stand for (a specular material anywhere).
MATTE_DEPTH1 = '"integer maxdepth" [1]'


class TestFurnace:
    """Constant environment light, no geometry: every ray escapes and picks
    up exactly L (InfiniteAreaLight::Le with no occlusion)."""

    @pytest.mark.parametrize("integrator", ["path", "directlighting", "whitted"])
    def test_escape_radiance(self, integrator):
        r = render_scene(
            scene_header(integrator, spp=4)
            + '''
WorldBegin
LightSource "infinite" "rgb L" [0.4 0.5 0.6]
WorldEnd
'''
        )
        img = r.image
        assert np.allclose(img[..., 0], 0.4, atol=1e-3)
        assert np.allclose(img[..., 1], 0.5, atol=1e-3)
        assert np.allclose(img[..., 2], 0.6, atol=1e-3)

    def test_furnace_flat_plane_path(self):
        """Lambertian plane of albedo rho in a uniform furnace of radiance
        1: a flat plane sees only the environment (it cannot see itself), so
        its exitant radiance is exactly rho — the single-scatter white
        furnace identity, integrating f*cos over the hemisphere."""
        r = render_scene(
            scene_header("path", spp=128, res=16, extra='"integer maxdepth" [8]')
            + f'''
WorldBegin
LightSource "infinite" "rgb L" [1 1 1]
Material "matte" "rgb Kd" [0.5 0.5 0.5]
Shape "trianglemesh" {QUAD} "point P" [-9 -9 2  9 -9 2  9 9 2  -9 9 2]
WorldEnd
'''
        )
        img = r.image
        center = img[6:10, 6:10].mean()
        assert abs(center - 0.5) < 0.02, f"furnace radiance {center} != 0.5"
