"""Device texture evaluation tests (VERDICT r3 #6).

Oracles:
- a checkerboard whose two arms are EQUAL must render bit-comparably to
  the constant-folded scene (texture machinery is an identity),
- a checkerboard matte plane lit head-on shows the two albedos in the
  expected spatial pattern (CPU-oracle predicted from uv layout),
- an imagemap round-trips: a 2x2 image sampled at cell centers under
  "repeat" reproduces the texel values (bilinear at centers),
- mip pyramid: each level is the box average of the previous,
- noise: FBm is deterministic, bounded, and non-constant.
"""

import numpy as np
import jax.numpy as jnp

from tests.test_render import MATTE_DEPTH1, QUAD, render_scene, scene_header


PLANE = f'''
AttributeBegin
Material "matte" "texture Kd" "kdtex"
Shape "trianglemesh" {QUAD}
  "point P" [-4 -4 0   4 -4 0   4 4 0   -4 4 0]
  "float uv" [0 0  4 0  4 4  0 4]
AttributeEnd
'''


def _lit(body, spp=8, res=32):
    return render_scene(
        scene_header("directlighting", spp=spp, res=res, extra=MATTE_DEPTH1)
        + '\nWorldBegin\n'
        + 'LightSource "distant" "rgb L" [3 3 3] "point from" [0 0 -1] "point to" [0 0 0]\n'
        + body
        + '\nWorldEnd\n'
    )


def test_equal_arm_checkerboard_matches_constant():
    tex = (
        'Texture "kdtex" "spectrum" "checkerboard" '
        '"rgb tex1" [0.4 0.5 0.6] "rgb tex2" [0.4 0.5 0.6]\n'
    )
    r_tex = _lit(tex + PLANE)
    const_plane = PLANE.replace(
        '"texture Kd" "kdtex"', '"rgb Kd" [0.4 0.5 0.6]'
    )
    r_const = _lit(const_plane)
    np.testing.assert_allclose(r_tex.image, r_const.image, rtol=1e-5, atol=1e-6)


def test_checkerboard_two_albedos_visible():
    tex = (
        'Texture "kdtex" "spectrum" "checkerboard" '
        '"rgb tex1" [0.9 0.9 0.9] "rgb tex2" [0.1 0.1 0.1]\n'
    )
    img = _lit(tex + PLANE, spp=16).image
    # the plane fills the view; uv in [0,4]^2 -> 16 alternating cells.
    # Both albedos must appear: bright pixels ~9x the dark ones.
    lum = img.mean(axis=-1)
    lo, hi = np.percentile(lum[lum > 1e-4], [10, 90])
    assert hi / max(lo, 1e-6) > 4.0, f"checker contrast missing: {lo} vs {hi}"


def test_imagemap_bilinear_roundtrip(tmp_path):
    from tpu_pbrt.utils.imageio import write_image

    img = np.zeros((2, 2, 3), np.float32)
    img[0, 0] = [1.0, 0.0, 0.0]
    img[0, 1] = [0.0, 1.0, 0.0]
    img[1, 0] = [0.0, 0.0, 1.0]
    img[1, 1] = [1.0, 1.0, 0.0]
    path = tmp_path / "t.pfm"
    write_image(str(path), img)

    from tpu_pbrt.core.texture_eval import build_texture_table

    node = (
        "imagemap",
        {
            "kind": "spectrum",
            "filename": str(path),
            "mapping": {"type": "uv", "su": 1.0, "sv": 1.0, "du": 0.0, "dv": 0.0},
            "trilerp": False,
            "max_aniso": 8.0,
            "wrap": "repeat",
            "scale": 1.0,
            "gamma": False,
        },
    )
    atlas, ev = build_texture_table([node])
    # texel centers: (0.25, 0.25) is texel (0,0) = row 0 col 0
    uv = jnp.asarray(
        [[0.25, 0.25], [0.75, 0.25], [0.25, 0.75], [0.75, 0.75]], jnp.float32
    )
    p = jnp.zeros((4, 3), jnp.float32)
    tid = jnp.zeros((4,), jnp.int32)
    out = np.asarray(ev(jnp.asarray(atlas), tid, uv, p))
    np.testing.assert_allclose(out[0], img[0, 0], atol=1e-5)
    np.testing.assert_allclose(out[1], img[0, 1], atol=1e-5)
    np.testing.assert_allclose(out[2], img[1, 0], atol=1e-5)
    np.testing.assert_allclose(out[3], img[1, 1], atol=1e-5)


def test_mip_pyramid_box_average():
    from tpu_pbrt.core.texture_eval import _build_pyramid

    rng = np.random.default_rng(0)
    img = rng.uniform(size=(8, 8, 3)).astype(np.float32)
    levels = _build_pyramid(img)
    assert [lv.shape[:2] for lv in levels] == [(8, 8), (4, 4), (2, 2), (1, 1)]
    np.testing.assert_allclose(levels[-1][0, 0], img.mean(axis=(0, 1)), rtol=1e-5)
    np.testing.assert_allclose(
        levels[1][0, 0], img[:2, :2].mean(axis=(0, 1)), rtol=1e-5
    )


def test_fbm_deterministic_bounded():
    from tpu_pbrt.core.texture_eval import fbm, noise3

    p = jnp.asarray(
        np.random.default_rng(1).uniform(-10, 10, (256, 3)), jnp.float32
    )
    n = np.asarray(noise3(p))
    assert np.all(np.abs(n) <= 1.5)
    assert n.std() > 0.05, "noise is (nearly) constant"
    f1 = np.asarray(fbm(p, 0.5, 6))
    f2 = np.asarray(fbm(p, 0.5, 6))
    np.testing.assert_array_equal(f1, f2)
    # lattice-point continuity: values at +eps and -eps agree
    q = jnp.asarray([[1.0, 2.0, 3.0]], jnp.float32)
    eps = 1e-3
    a = float(noise3(q - eps)[0])
    b = float(noise3(q + eps)[0])
    assert abs(a - b) < 0.05


def test_ewa_anisotropic_preserves_cross_axis_detail(tmp_path):
    """mipmap.h MIPMap::EWA semantics (VERDICT r4 #7): a footprint that
    is wide along u but narrow along v must average along u WITHOUT
    blurring across v. The isotropic trilinear path (scalar lod = max
    axis) picks the coarse level and destroys the stripes; the EWA
    filter keys the level off the MINOR axis and keeps them."""
    from tpu_pbrt.utils.imageio import write_image

    # horizontal stripes: value depends only on v (8-texel period rows)
    img = np.zeros((64, 64, 3), np.float32)
    img[(np.arange(64) // 8 % 2 == 0), :, :] = 1.0
    path = tmp_path / "stripes.pfm"
    write_image(str(path), img)

    from tpu_pbrt.core.texture_eval import build_texture_table

    node = (
        "imagemap",
        {
            "kind": "spectrum",
            "filename": str(path),
            "mapping": {"type": "uv", "su": 1.0, "sv": 1.0, "du": 0.0,
                        "dv": 0.0},
            "trilerp": False,
            "max_aniso": 8.0,
            "wrap": "repeat",
            "scale": 1.0,
            "gamma": False,
        },
    )
    atlas, ev = build_texture_table([node])
    a = jnp.asarray(atlas)
    # center of a white stripe (v around 0.0625 = row 4 of 64)
    uv = jnp.asarray([[0.5, 4.5 / 64.0]], jnp.float32)
    p = jnp.zeros((1, 3), jnp.float32)
    tid = jnp.zeros((1,), jnp.int32)

    # anisotropic footprint: wide along u, a texel along v
    duv4 = jnp.asarray([[0.25, 0.0, 0.0, 1.0 / 64.0]], jnp.float32)
    out_ewa = float(np.asarray(ev(a, tid, uv, p, duv4))[0, 0])
    # isotropic path at the same MAX width (the old behavior)
    out_iso = float(
        np.asarray(ev(a, tid, uv, p, jnp.full((1,), 0.25, jnp.float32)))[0, 0]
    )
    assert out_ewa > 0.85, f"EWA blurred across the minor axis: {out_ewa}"
    assert out_iso < 0.7, (
        f"isotropic reference unexpectedly sharp ({out_iso}) — "
        "the oracle no longer discriminates"
    )


def test_ewa_isotropic_footprint_matches_trilinear():
    """A circular footprint must reduce EWA to (approximately) the
    single-tap trilinear result — the taps collapse onto the same
    ellipse and the Gaussian weights normalize out."""
    from tpu_pbrt.core.texture_eval import build_texture_table

    rng = np.random.default_rng(7)
    # procedural checker node needs no file; use an imagemap-free
    # comparison via a synthetic imagemap written to tmp — instead
    # reuse fbm-free path: build a small random pfm in-memory
    import tempfile

    from tpu_pbrt.utils.imageio import write_image

    img = rng.uniform(size=(32, 32, 3)).astype(np.float32)
    with tempfile.NamedTemporaryFile(suffix=".pfm", delete=False) as f:
        path = f.name
    write_image(path, img)
    node = (
        "imagemap",
        {
            "kind": "spectrum",
            "filename": path,
            "mapping": {"type": "uv", "su": 1.0, "sv": 1.0, "du": 0.0,
                        "dv": 0.0},
            "trilerp": False,
            "max_aniso": 8.0,
            "wrap": "repeat",
            "scale": 1.0,
            "gamma": False,
        },
    )
    atlas, ev = build_texture_table([node])
    a = jnp.asarray(atlas)
    n = 16
    uv = jnp.asarray(rng.uniform(0.1, 0.9, (n, 2)), jnp.float32)
    p = jnp.zeros((n, 3), jnp.float32)
    tid = jnp.zeros((n,), jnp.int32)
    w = 0.1
    duv4 = jnp.tile(jnp.asarray([[w, 0.0, 0.0, w]], jnp.float32), (n, 1))
    out_ewa = np.asarray(ev(a, tid, uv, p, duv4))
    out_tri = np.asarray(ev(a, tid, uv, p, jnp.full((n,), w, jnp.float32)))
    # same level, taps spread across one footprint width: close, not exact
    assert np.max(np.abs(out_ewa - out_tri)) < 0.15
    import os

    os.unlink(path)
