"""Participating media: transmittance, distance sampling, phase functions.

Capability match for pbrt-v3:
- src/core/medium.{h,cpp}: Medium::Tr/Sample interfaces, HenyeyGreenstein
  phase function (p(cos), Sample_p), and the measured subsurface medium
  presets (GetMediumScatteringProperties — the ~60 entries reduce to the
  handful the target scenes use; others fall back with a warning).
- src/media/homogeneous.cpp: closed-form Beer-Lambert Tr, spectral channel
  distance sampling with the 1/n channel-average pdf.
- src/media/grid.cpp GridDensityMedium: trilinearly interpolated density,
  ratio-tracking Tr and delta-tracking distance sampling, lowered to
  bounded lax.while_loop (the TPU equivalent of the reference's
  unbounded while loops).

Media are a SoA table (type enum + sigma_a/sigma_s/g) plus an optional
density grid; rays carry a current-medium id (-1 = vacuum).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_pbrt.core.sampling import uniform_float
from tpu_pbrt.core.vecmath import coordinate_system, dot, linear3, normalize
from tpu_pbrt.parallel.mesh import vary
from tpu_pbrt.utils.error import Warning

MEDIUM_NONE = -1
MEDIUM_HOMOGENEOUS = 0
MEDIUM_GRID = 1

# pbrt medium.cpp SubsurfaceParameterTable (sigma_prime_s, sigma_a) —
# the entries plausibly used by the target configs
MEDIUM_PRESETS = {
    "milk": (np.array([2.55, 3.21, 3.77]), np.array([0.0011, 0.0024, 0.014])),
    "skimmilk": (np.array([0.70, 1.22, 1.90]), np.array([0.0014, 0.0025, 0.0142])),
    "wholemilk": (np.array([2.55, 3.21, 3.77]), np.array([0.0011, 0.0024, 0.014])),
    "skin1": (np.array([0.74, 0.88, 1.01]), np.array([0.032, 0.17, 0.48])),
    "skin2": (np.array([1.09, 1.59, 1.79]), np.array([0.013, 0.070, 0.145])),
    "marble": (np.array([2.19, 2.62, 3.00]), np.array([0.0021, 0.0041, 0.0071])),
    "cream": (np.array([7.38, 5.47, 3.15]), np.array([0.0002, 0.0028, 0.0163])),
    "ketchup": (np.array([0.18, 0.07, 0.03]), np.array([0.061, 0.97, 1.45])),
    "coke": (np.array([0.01, 0.01, 0.01]), np.array([0.10014, 0.16503, 0.2468])),
}


class MediumTable(NamedTuple):
    """Device SoA of media rows; grids stored side-band (single grid slot —
    target configs use one heterogeneous medium per scene; extendable to an
    atlas)."""

    mtype: jnp.ndarray  # (M,)
    sigma_a: jnp.ndarray  # (M,3)
    sigma_s: jnp.ndarray  # (M,3)
    g: jnp.ndarray  # (M,)
    # grid medium support
    grid_id: jnp.ndarray  # (M,) -1 or 0
    density: jnp.ndarray  # (D,H,W) or (1,1,1) placeholder
    world_to_medium: jnp.ndarray  # (4,4)
    sigma_t_max: jnp.ndarray  # scalar: majorant for delta tracking


def empty_medium_table() -> MediumTable:
    return MediumTable(
        mtype=jnp.zeros((1,), jnp.int32),
        sigma_a=jnp.zeros((1, 3), jnp.float32),
        sigma_s=jnp.zeros((1, 3), jnp.float32),
        g=jnp.zeros((1,), jnp.float32),
        grid_id=jnp.full((1,), -1, jnp.int32),
        density=jnp.zeros((1, 1, 1), jnp.float32),
        world_to_medium=jnp.eye(4, dtype=jnp.float32),
        sigma_t_max=jnp.float32(0.0),
    )


# -------------------------------------------------------------------------
# Henyey-Greenstein (medium.cpp)
# -------------------------------------------------------------------------

def hg_p(cos_theta, g):
    denom = 1.0 + g * g + 2.0 * g * cos_theta
    return (1.0 / (4.0 * jnp.pi)) * (1.0 - g * g) / (denom * jnp.sqrt(jnp.maximum(denom, 1e-9)))


def hg_sample(wo, g, u1, u2):
    """HenyeyGreenstein::Sample_p: returns (wi, pdf=p)."""
    g_safe = jnp.where(jnp.abs(g) < 1e-3, jnp.where(g < 0, -1e-3, 1e-3), g)
    sq = (1.0 - g_safe * g_safe) / (1.0 + g_safe - 2.0 * g_safe * u1)
    cos_theta_hg = -(1.0 + g_safe * g_safe - sq * sq) / (2.0 * g_safe)
    cos_theta = jnp.where(jnp.abs(g) < 1e-3, 1.0 - 2.0 * u1, cos_theta_hg)
    sin_theta = jnp.sqrt(jnp.maximum(0.0, 1.0 - cos_theta * cos_theta))
    phi = 2.0 * jnp.pi * u2
    # build frame around wo (pbrt samples w.r.t. wo direction)
    v1, v2 = coordinate_system(wo)
    wi = (
        sin_theta[..., None] * jnp.cos(phi)[..., None] * v1
        + sin_theta[..., None] * jnp.sin(phi)[..., None] * v2
        + cos_theta[..., None] * wo
    )
    return wi, hg_p(cos_theta, g)


# -------------------------------------------------------------------------
# Grid density lookup (media/grid.cpp GridDensityMedium::Density)
# -------------------------------------------------------------------------

def grid_density(mt: MediumTable, p_world):
    """Trilinear density at world points (vectorized)."""
    m = mt.world_to_medium
    p = linear3(m[:3, :3], p_world) + m[:3, 3]
    d, h, w = mt.density.shape
    # medium space is [0,1]^3 over the grid
    gx = p[..., 0] * w - 0.5
    gy = p[..., 1] * h - 0.5
    gz = p[..., 2] * d - 0.5
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    z0 = jnp.floor(gz).astype(jnp.int32)
    fx, fy, fz = gx - x0, gy - y0, gz - z0

    def tap(xi, yi, zi):
        inb = (xi >= 0) & (xi < w) & (yi >= 0) & (yi < h) & (zi >= 0) & (zi < d)
        v = mt.density[jnp.clip(zi, 0, d - 1), jnp.clip(yi, 0, h - 1), jnp.clip(xi, 0, w - 1)]
        return jnp.where(inb, v, 0.0)

    d00 = tap(x0, y0, z0) * (1 - fx) + tap(x0 + 1, y0, z0) * fx
    d10 = tap(x0, y0 + 1, z0) * (1 - fx) + tap(x0 + 1, y0 + 1, z0) * fx
    d01 = tap(x0, y0, z0 + 1) * (1 - fx) + tap(x0 + 1, y0, z0 + 1) * fx
    d11 = tap(x0, y0 + 1, z0 + 1) * (1 - fx) + tap(x0 + 1, y0 + 1, z0 + 1) * fx
    d0 = d00 * (1 - fy) + d10 * fy
    d1 = d01 * (1 - fy) + d11 * fy
    inside = (p[..., 0] >= 0) & (p[..., 0] <= 1) & (p[..., 1] >= 0) & (p[..., 1] <= 1) & (
        p[..., 2] >= 0
    ) & (p[..., 2] <= 1)
    return jnp.where(inside, d0 * (1 - fz) + d1 * fz, 0.0)


_MAX_TRACKING_STEPS = 256


def medium_tr(mt: MediumTable, med_id, o, d, t_max, px, py, s, salt):
    """Medium::Tr along [0, t_max] for each ray's current medium.

    Homogeneous: exp(-sigma_t * t). Grid: ratio tracking with the grid
    majorant (grid.cpp GridDensityMedium::Tr), bounded steps."""
    active = med_id >= 0
    idx = jnp.maximum(med_id, 0)
    sig_t = mt.sigma_a[idx] + mt.sigma_s[idx]
    t_cl = jnp.minimum(t_max, 1e7)  # avoid inf * 0
    tr_homog = jnp.exp(-sig_t * t_cl[..., None])

    if int(mt.density.size) > 1:
        inv_max = 1.0 / jnp.maximum(mt.sigma_t_max, 1e-9)
        sig_t1 = sig_t[..., 0]  # grid media are monochromatic-sigma in pbrt

        def body(i, carry):
            t, tr = carry
            u = uniform_float(px, py, s, salt + 3000 + i)
            t = t - jnp.log(1.0 - u) * inv_max
            dens = grid_density(mt, o + t[..., None] * d)
            live = t < t_max
            tr = jnp.where(live, tr * (1.0 - jnp.maximum(0.0, dens * sig_t1 * inv_max)), tr)
            return t, tr

        t0 = jnp.zeros_like(t_cl)
        tr0 = jnp.ones_like(t_cl)
        _, tr_grid = jax.lax.fori_loop(
            0, _MAX_TRACKING_STEPS, body, vary((t0, tr0))
        )
        is_grid = mt.mtype[idx] == MEDIUM_GRID
        tr = jnp.where(is_grid[..., None], tr_grid[..., None], tr_homog)
    else:
        tr = tr_homog
    return jnp.where(active[..., None], tr, 1.0)


class MediumSample(NamedTuple):
    sampled_medium: jnp.ndarray  # (R,) bool — interaction inside the medium
    t: jnp.ndarray  # (R,) interaction distance
    weight: jnp.ndarray  # (R,3) beta multiplier (Tr*sigma_s/pdf or Tr/pdf)


def medium_sample(mt: MediumTable, med_id, o, d, t_hit, px, py, s, salt) -> MediumSample:
    """Medium::Sample along a ray segment ending at the surface hit t_hit.

    Homogeneous (homogeneous.cpp): pick a spectral channel uniformly,
    sample an exponential distance, weight by Tr*sigma_s/pdf (medium) or
    Tr/pdf (surface). Grid (grid.cpp): delta tracking against the majorant."""
    active = med_id >= 0
    idx = jnp.maximum(med_id, 0)
    sig_a = mt.sigma_a[idx]
    sig_s = mt.sigma_s[idx]
    sig_t = sig_a + sig_s
    t_end = jnp.minimum(t_hit, 1e7)

    # ---- homogeneous ----------------------------------------------------
    uc = uniform_float(px, py, s, salt)
    ud = uniform_float(px, py, s, salt + 1)
    ch = jnp.minimum((uc * 3).astype(jnp.int32), 2)
    sig_ch = jnp.take_along_axis(sig_t, ch[..., None], axis=-1)[..., 0]
    t_s = -jnp.log(jnp.maximum(1.0 - ud, 1e-20)) / jnp.maximum(sig_ch, 1e-20)
    in_medium_h = (t_s < t_end) & (sig_ch > 0)
    t_m = jnp.minimum(t_s, t_end)
    tr = jnp.exp(-sig_t * t_m[..., None])
    # pdf: average over channels
    pdf_m = jnp.mean(sig_t * tr, axis=-1)
    pdf_surf = jnp.mean(tr, axis=-1)
    w_medium = tr * sig_s / jnp.maximum(pdf_m, 1e-20)[..., None]
    w_surface = tr / jnp.maximum(pdf_surf, 1e-20)[..., None]
    weight_h = jnp.where(in_medium_h[..., None], w_medium, w_surface)

    if int(mt.density.size) > 1:
        # ---- grid: delta tracking --------------------------------------
        inv_max = 1.0 / jnp.maximum(mt.sigma_t_max, 1e-9)
        sig_t1 = sig_t[..., 0]
        albedo = sig_s[..., 0] / jnp.maximum(sig_t1, 1e-20)

        def body(i, carry):
            t, done, hit_med = carry
            u1 = uniform_float(px, py, s, salt + 5000 + 2 * i)
            u2 = uniform_float(px, py, s, salt + 5001 + 2 * i)
            t_new = t - jnp.log(1.0 - u1) * inv_max
            esc = t_new >= t_end
            dens = grid_density(mt, o + t_new[..., None] * d)
            real = u2 < dens * sig_t1 * inv_max
            newly_done = ~done & (esc | real)
            hit_med = jnp.where(~done & real & ~esc, True, hit_med)
            t = jnp.where(done, t, t_new)
            return t, done | newly_done, hit_med

        t0 = jnp.zeros_like(t_end)
        f0 = jnp.zeros_like(t_end, dtype=bool)
        t_g, _, hit_med_g = jax.lax.fori_loop(
            0, _MAX_TRACKING_STEPS, body, vary((t0, f0, f0))
        )
        is_grid = mt.mtype[idx] == MEDIUM_GRID
        in_medium = jnp.where(is_grid, hit_med_g, in_medium_h)
        t_m = jnp.where(is_grid, jnp.minimum(t_g, t_end), t_m)
        # delta tracking weight: sigma_s/sigma_t on real collision, 1 on escape
        w_grid = jnp.where(hit_med_g[..., None], albedo[..., None].repeat(3, -1), 1.0)
        weight = jnp.where(is_grid[..., None], w_grid, weight_h)
    else:
        in_medium = in_medium_h
        weight = weight_h

    in_medium = in_medium & active
    weight = jnp.where(active[..., None], weight, 1.0)
    return MediumSample(in_medium, t_m, weight)
