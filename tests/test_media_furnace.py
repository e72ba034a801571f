"""The volume furnace: split off tests/test_media.py by cold cost
(ISSUE 28)."""

import numpy as np
import pytest

from tests.test_render import render_scene


class TestVolumeFurnace:
    """VERDICT r4 #9: a closed-form in-scattering oracle. A camera at
    the center of a uniformly emitting sphere filled with a purely
    scattering medium must see EXACTLY the shell radiance L0 for any
    scattering coefficient and phase anisotropy (radiative transfer in
    a uniform isotropic field is the identity when sigma_a = 0) —
    exercising distance sampling, HG phase sampling, NEE-with-Tr, and
    multiple scattering at once.

    `volpath` unrolls one bounce of its program per `maxdepth`, and
    XLA:CPU's time grows faster than the depth: the 12-deep program is
    296 s of compile cold and 94 s a render (421 s for the first case
    alone on an idle core, ISSUE 28), so tier-1 holds the identity at
    half the optical depth and half the bounces and the deep pair is
    `slow`.

    The shallow pair's envelope is set from what it reads (PR 28, CPU):
    2.00048 at g = 0 and 1.99751 at g = 0.5, within 0.13 % of L0. The
    same scene cut at `maxdepth` 4 reads 0.75 % / 0.42 % low, at 3
    2.35 % / 1.34 %, at 2 6.9 % / 4.5 %: 1 % fails whatever loses the
    paths past their third scattering. The deep pair loses more to its
    own cut at 12 bounces and keeps the 8 % it always had."""

    @pytest.mark.parametrize("g", [0.0, 0.5])
    @pytest.mark.parametrize("sigma_s,maxdepth,envelope", [
        (0.12, 6, 0.01),  # tau = 0.6 to the shell: 45 % of paths scatter, 20 % twice
        pytest.param(  # tau = 1.25
            0.25, 12, 0.08, marks=[pytest.mark.slow, pytest.mark.case_limit(900)]
        ),
    ], ids=["tau0.6", "tau1.25"])
    def test_scattering_furnace(self, sigma_s, maxdepth, envelope, g):
        L0 = 2.0
        r = render_scene(
            f'''
Integrator "volpath" "integer maxdepth" [{maxdepth}]
Sampler "halton" "integer pixelsamples" [256]
PixelFilter "box"
Film "image" "integer xresolution" [8] "integer yresolution" [8] "string filename" [""]
LookAt 0 0 0  0 0 1  0 1 0
MakeNamedMedium "fog" "string type" "homogeneous" "rgb sigma_a" [0 0 0] "rgb sigma_s" [{sigma_s} {sigma_s} {sigma_s}] "float g" [{g}]
MediumInterface "" "fog"
Camera "perspective" "float fov" [60]
WorldBegin
AttributeBegin
  # black-bodied pure emitter: a reflective shell would multiply the
  # furnace by 1/(1-rho)
  Material "matte" "rgb Kd" [0 0 0]
  AreaLightSource "diffuse" "rgb L" [{L0} {L0} {L0}] "bool twosided" ["true"]
  Shape "sphere" "float radius" [5]
AttributeEnd
WorldEnd
'''
        )
        img = np.asarray(r.image)
        got = float(img.mean())
        assert np.isfinite(img).all()
        assert abs(got - L0) / L0 < envelope, (got, L0, g)
