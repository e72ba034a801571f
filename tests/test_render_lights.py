"""Analytic light oracles: each light kind over a matte plane under the
direct-lighting integrator, against its closed form. Split off
tests/test_render.py by cold cost (ISSUE 28).

The point, distant and shadow cases render through the DEFAULT depth, the
program a user's `Integrator "directlighting"` runs: they hold the masking
of dead lanes at depths 1 to 4 to a closed form (without it a matte hit
would read about five times its radiance). The area light here and the
image lights of tests/test_render_small.py are matte-only scenes at
MATTE_DEPTH1, which test_default_depth_equals_depth1_on_matte holds to
the default bit for bit.
"""

import functools

import numpy as np

from tests.test_render import MATTE_DEPTH1, QUAD, render_scene, scene_header

POINT_OVER_PLANE = f'''
WorldBegin
LightSource "point" "rgb I" [10 10 10] "point from" [0 0 0]
Material "matte" "rgb Kd" [0.6 0.4 0.2]
Shape "trianglemesh" {QUAD} "point P" [-9 -9 2  9 -9 2  9 9 2  -9 9 2]
WorldEnd
'''


@functools.lru_cache(maxsize=None)
def point_over_plane_default_depth():
    """One render for the two cases that read it."""
    return render_scene(scene_header("directlighting", spp=16) + POINT_OVER_PLANE)


class TestAnalyticDirect:
    def test_area_light_seen_directly(self):
        """Camera ray hits the emissive quad -> pixel = Le exactly."""
        r = render_scene(
            scene_header("directlighting", spp=4, extra=MATTE_DEPTH1)
            + f'''
WorldBegin
AttributeBegin
  AreaLightSource "diffuse" "rgb L" [3 2 1]
  # winding chosen so the geometric normal faces the camera (-z)
  Shape "trianglemesh" {QUAD} "point P" [-2 -2 0  -2 2 0  2 2 0  2 -2 0]
AttributeEnd
WorldEnd
'''
        )
        img = r.image
        c = img[16, 16]
        assert np.allclose(c, [3, 2, 1], rtol=1e-3), c

    def test_point_light_lambertian_analytic(self):
        """Point light I over a lambertian plane: L = (Kd/pi) * I cos/r^2,
        checked at the image center against the closed form."""
        I = np.array([10.0, 10.0, 10.0])
        kd = np.array([0.6, 0.4, 0.2])
        # plane z=2 facing camera at origin... camera at (0,0,-3) looking +z
        # light at (0, 0, 0): center hit point (0,0,2), r=2, cos=1
        img = point_over_plane_default_depth().image
        expected = kd / np.pi * I * 1.0 / 4.0
        got = img[15:17, 15:17].mean(axis=(0, 1))
        assert np.allclose(got, expected, rtol=0.02), (got, expected)

    def test_default_depth_equals_depth1_on_matte(self):
        """`directlighting` goes on past a hit along specular bounces only,
        so on a matte scene depths 1 to 4 of the default program must add
        nothing: no radiance and no ray. This is what lets the other
        matte-only cases of the suite take MATTE_DEPTH1."""
        deep = point_over_plane_default_depth()
        flat = render_scene(
            scene_header("directlighting", spp=16, extra=MATTE_DEPTH1) + POINT_OVER_PLANE
        )
        assert flat.image.tobytes() == deep.image.tobytes()
        assert flat.rays_traced == deep.rays_traced
        # one camera ray a sample, two rays of direct lighting at its hit
        assert deep.rays_traced == 32 * 32 * 16 * 3

    def test_distant_light_analytic(self):
        """Distant light L along -z onto a facing plane: Lo = Kd/pi * L.
        16 spp like the point light's case: the light's kind and numbers are
        arguments of the chunk program, so the two scenes share one."""
        r = render_scene(
            scene_header("directlighting", spp=16)
            + f'''
WorldBegin
LightSource "distant" "rgb L" [2 2 2] "point from" [0 0 -1] "point to" [0 0 0]
Material "matte" "rgb Kd" [0.5 0.5 0.5]
Shape "trianglemesh" {QUAD} "point P" [-9 -9 2  9 -9 2  9 9 2  -9 9 2]
WorldEnd
'''
        )
        img = r.image
        expected = 0.5 / np.pi * 2.0
        got = img[14:18, 14:18].mean()
        assert abs(got - expected) < 0.01 * expected + 1e-4, (got, expected)

    def test_shadow(self):
        """A small occluder near the light casts a shadow larger than its
        own silhouette: plane points beside the occluder (visible to the
        camera) are dark inside the umbra and lit outside it."""
        r = render_scene(
            scene_header("directlighting", spp=4)
            + f'''
WorldBegin
LightSource "point" "rgb I" [10 10 10] "point from" [0 0 0.5]
Material "matte" "rgb Kd" [0.5 0.5 0.5]
Shape "trianglemesh" {QUAD} "point P" [-9 -9 2  9 -9 2  9 9 2  -9 9 2]
Shape "trianglemesh" {QUAD} "point P" [-0.3 -0.3 1  0.3 -0.3 1  0.3 0.3 1  -0.3 0.3 1]
WorldEnd
'''
        )
        img = r.image
        # umbra on the plane reaches |x| = 0.3*(2-0.5)/(1-0.5) = 0.9;
        # the occluder hides only |x| < ~0.375 of the plane from the camera.
        # pixel col 19 -> plane x ~ 0.64 (shadowed, visible); col 28 -> ~2.2 (lit)
        assert img[16, 19].max() < 0.01, img[16, 19]
        assert img[16, 28].mean() > 0.03, img[16, 28]
