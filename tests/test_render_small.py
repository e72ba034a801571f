"""The render oracles that cost least cold: path against directlighting on
a direct-only scene, the mirror, the image-modulated lights. Split off
tests/test_render.py and tests/test_render_lights.py by cold cost (ISSUE 28):
moved, not changed.
"""

import numpy as np

from tests.test_render import MATTE_DEPTH1, QUAD, render_scene, scene_header


class TestCrossIntegrator:
    def test_path_matches_direct_on_direct_only_scene(self):
        """On a scene with one bounce of transport (maxdepth=1), the path
        integrator and direct-lighting integrator estimate the same
        integral — the cross-convergence oracle from SURVEY.md §4."""
        scene_body = f'''
WorldBegin
AttributeBegin
  AreaLightSource "diffuse" "rgb L" [8 8 8]
  Translate 0 1.8 0
  Shape "trianglemesh" {QUAD} "point P" [-0.6 0 -0.6  0.6 0 -0.6  0.6 0 0.6  -0.6 0 0.6]
AttributeEnd
Material "matte" "rgb Kd" [0.7 0.6 0.5]
Shape "trianglemesh" {QUAD} "point P" [-2 -2 2  2 -2 2  2 2 2  -2 2 2]
Shape "trianglemesh" {QUAD} "point P" [-2 -2 -4  2 -2 -4  2 -2 2  -2 -2 2]
WorldEnd
'''
        r1 = render_scene(
            scene_header("directlighting", spp=128, res=24, extra='"integer maxdepth" [1]')
            + scene_body
        )
        r2 = render_scene(
            scene_header("path", spp=128, res=24, extra='"integer maxdepth" [1]') + scene_body
        )
        a, b = r1.image, r2.image
        mse = float(np.mean((a - b) ** 2))
        scale = float(np.mean(a**2)) + 1e-9
        assert mse / scale < 0.01, f"relative MSE {mse / scale}"


class TestSpecular:
    def test_mirror_reflects_light(self):
        """Mirror plane reflecting an area light: the reflected image of the
        light carries Le * Kr."""
        r = render_scene(
            scene_header("path", spp=32, extra='"integer maxdepth" [3]')
            + f'''
WorldBegin
AttributeBegin
  AreaLightSource "diffuse" "rgb L" [5 5 5]
  Shape "trianglemesh" {QUAD} "point P" [-2 -2 -3.05  2 -2 -3.05  2 2 -3.05  -2 2 -3.05]
AttributeEnd
Material "mirror" "rgb Kr" [0.8 0.8 0.8]
Shape "trianglemesh" {QUAD} "point P" [-2 -2 2  2 -2 2  2 2 2  -2 2 2]
WorldEnd
'''
        )
        img = r.image
        got = img[16, 16]
        assert np.allclose(got, 5 * 0.8, rtol=0.05), got


class TestImageLights:
    """Goniometric/projection lights: image-modulated point intensity
    (goniometric.cpp / projection.cpp capability)."""

    def _plane_scene(self, light, mapline=""):
        return (
            scene_header("directlighting", spp=4, res=24, extra=MATTE_DEPTH1)
            + f'''
WorldBegin
{light}
Material "matte" "rgb Kd" [1 1 1]
Shape "trianglemesh" {QUAD} "point P" [-4 -4 1   4 -4 1   4 4 1  -4 4 1]
WorldEnd
'''
        )

    def test_gonio_constant_map_matches_point(self, tmp_path):
        import numpy as np
        from tpu_pbrt.utils.imageio import write_image

        m = str(tmp_path / "m.pfm")
        write_image(m, np.full((4, 8, 3), 1.0, np.float32))
        r_g = render_scene(self._plane_scene(
            f'LightSource "goniometric" "rgb I" [5 5 5] "string mapname" ["{m}"]'
        ))
        r_p = render_scene(self._plane_scene(
            'LightSource "point" "rgb I" [5 5 5]'
        ))
        np.testing.assert_allclose(r_g.image, r_p.image, rtol=1e-4, atol=1e-5)

    def test_projection_lights_only_inside_fov(self, tmp_path):
        import numpy as np
        from tpu_pbrt.utils.imageio import write_image

        m = str(tmp_path / "m.pfm")
        write_image(m, np.full((8, 8, 3), 1.0, np.float32))
        img = render_scene(self._plane_scene(
            f'LightSource "projection" "rgb I" [5 5 5] "float fov" [30] '
            f'"string mapname" ["{m}"]'
        )).image
        lum = img.mean(-1)
        assert lum.max() > 1e-3, "projection light contributed nothing"
        # the 30-degree frustum lights only the central patch of the plane
        assert lum[0, 0] == 0.0 and lum[-1, -1] == 0.0
        c = lum.shape[0] // 2
        assert lum[c, c] > 0.0
