"""Null-material interfaces around participating media: split off
tests/test_media.py by cold cost (ISSUE 28)."""

import numpy as np

from tests.test_render import render_scene


class TestNullInterface:
    """ADVICE r1 (high): MAT_NONE container geometry must not occlude NEE
    shadow rays — pbrt VisibilityTester::Tr passes through null-BSDF
    surfaces accumulating per-segment transmittance."""

    CUBE = (
        'Shape "trianglemesh" "integer indices" '
        "[0 1 2 0 2 3  4 6 5 4 7 6  0 4 1 1 4 5  2 6 3 3 6 7  1 5 2 2 5 6  0 3 7 0 7 4] "
        '"point P" [-1 -1 -1  1 -1 -1  1 -1 1  -1 -1 1  -1 1 -1  1 1 -1  1 1 1  -1 1 1]'
    )

    def test_bounded_medium_not_black(self):
        """Scattering medium inside a null-material container, light
        outside: in-medium direct lighting must pass through the container
        walls (the cloud.pbrt topology)."""
        r = render_scene(
            f'''
Integrator "volpath" "integer maxdepth" [3]
Sampler "halton" "integer pixelsamples" [64]
PixelFilter "box"
Film "image" "integer xresolution" [16] "integer yresolution" [16] "string filename" [""]
LookAt 0 0 -4  0 0 0  0 1 0
Camera "perspective" "float fov" [40]
MakeNamedMedium "cloud" "string type" "homogeneous" "rgb sigma_a" [0.05 0.05 0.05] "rgb sigma_s" [0.8 0.8 0.8] "float g" [0.0]
WorldBegin
LightSource "point" "rgb I" [40 40 40] "point from" [0 3 0]
AttributeBegin
  Material "none"
  MediumInterface "cloud" ""
  {self.CUBE}
AttributeEnd
WorldEnd
'''
        )
        img = np.asarray(r.image)
        center = float(img[6:10, 6:10].mean())
        assert center > 0.005, f"in-medium NEE is black through the container: {center}"

    def test_null_quad_does_not_occlude_path(self):
        """path integrator: a null-material quad between an area light and
        a matte floor must neither block the light (NEE) nor silhouette the
        continuation rays."""
        body = '''
WorldBegin
AttributeBegin
  AreaLightSource "diffuse" "rgb L" [10 10 10]
  Translate 0 2 0
  Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-0.8 0 -0.8  0.8 0 -0.8  0.8 0 0.8  -0.8 0 0.8]
AttributeEnd
{blocker}
Material "matte" "rgb Kd" [0.7 0.7 0.7]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-2 -1 -2  2 -1 -2  2 -1 2  -2 -1 2]
WorldEnd
'''
        hdr = '''
Integrator "path" "integer maxdepth" [3]
Sampler "halton" "integer pixelsamples" [128]
PixelFilter "box"
Film "image" "integer xresolution" [16] "integer yresolution" [16] "string filename" [""]
LookAt 0 0.4 -3.5  0 -0.4 0  0 1 0
Camera "perspective" "float fov" [45]
'''
        null_quad = (
            'AttributeBegin\n  Material "none"\n'
            '  Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] '
            '"point P" [-1.5 0.5 -1.5  1.5 0.5 -1.5  1.5 0.5 1.5  -1.5 0.5 1.5]\nAttributeEnd\n'
        )
        r_null = render_scene(hdr + body.format(blocker=null_quad))
        r_open = render_scene(hdr + body.format(blocker=""))
        m_null = float(np.asarray(r_null.image).mean())
        m_open = float(np.asarray(r_open.image).mean())
        assert m_open > 0.01
        assert abs(m_null - m_open) / m_open < 0.05, (m_null, m_open)
