"""BDPT cross-convergence tests (VERDICT r3 #4: bdpt mean ~= path mean
within noise on the cornell box — the upstream ecosystem's integrator
cross-check, mirroring pbrt's analytic-scenes strategy)."""

import numpy as np

from tpu_pbrt.scenes import compile_api, make_cornell


def _render(integrator, md, spp=64, res=20, only=None):
    api = make_cornell(res=res, spp=spp, integrator=integrator, maxdepth=md)
    scene, integ = compile_api(api)
    if only is not None:
        integ._only = only
    return np.asarray(integ.render(scene).image)


def test_bdpt_matches_path_direct():
    """maxdepth=1: bdpt's (0,2)+(1,2)+(2,1) strategies must reproduce
    direct lighting exactly (the MIS weights must partition each path
    family, not double count it)."""
    p = _render("path", 1)
    b = _render("bdpt", 1)
    rel = abs(b.mean() - p.mean()) / p.mean()
    assert rel < 0.05, f"bdpt {b.mean():.4f} vs path {p.mean():.4f} ({rel:.1%})"


def test_bdpt_matches_path_indirect():
    """maxdepth=3: full strategy matrix incl. s>=2 connections and
    light-tracing splats."""
    p = _render("path", 3)
    b = _render("bdpt", 3)
    rel = abs(b.mean() - p.mean()) / p.mean()
    assert rel < 0.05, f"bdpt {b.mean():.4f} vs path {p.mean():.4f} ({rel:.1%})"
    # per-channel agreement too (catches color-channel MIS asymmetries)
    pc, bc = p.mean(axis=(0, 1)), b.mean(axis=(0, 1))
    np.testing.assert_allclose(bc, pc, rtol=0.08)


def test_bdpt_light_tracing_splats_land():
    """The t=1 family renders through Film::AddSplat: restricted to the
    (2,1) strategy the image must be non-zero and concentrated where the
    directly lit geometry is."""
    img = _render("bdpt", 2, only={(2, 1)})
    assert img.mean() > 1e-3, "light-tracing splats produced a black image"
    assert np.isfinite(img).all()
