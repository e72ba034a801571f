"""Device-side BSDF evaluation and sampling (tagged-union dispatch).

Capability match for pbrt-v3 src/core/reflection.{h,cpp} and
src/core/microfacet.{h,cpp}:
- Fresnel{Dielectric,Conductor,NoOp}
- LambertianReflection/Transmission, OrenNayar
- SpecularReflection/Transmission, FresnelSpecular
- MicrofacetReflection (TrowbridgeReitz/GGX with visible-normal sampling)
- FresnelBlend (substrate)
and for the per-material BxDF assembly in src/materials/*::
ComputeScatteringFunctions (matte/plastic/metal/glass/mirror/uber/
substrate/translucent/disney lowered to lobe combinations).

TPU-first design: instead of arena-allocated BxDF object stacks with
virtual dispatch, every ray carries its gathered material parameters
(SoA row) and the whole batch evaluates a fixed set of lobe formulas under
masks — a diffuse lobe and a glossy/specular lobe per material, combined
with pbrt's matching-lobe pdf averaging. All directions are in the local
shading frame (z = shading normal). Radiance-mode transport (eta^2 scaling
on specular transmission) matches pbrt's TransportMode::Radiance.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from tpu_pbrt.core.vecmath import (
    abs_cos_theta,
    cos2_theta,
    cos_phi,
    cos_theta,
    same_hemisphere,
    sin2_theta,
    sin_phi,
    tan2_theta,
    tan_theta,
)
from tpu_pbrt.core.sampling import (
    concentric_sample_disk,
    cosine_hemisphere_pdf,
    cosine_sample_hemisphere,
)
from tpu_pbrt.scene.compiler import (
    MAT_DISNEY,
    MAT_GLASS,
    MAT_MATTE,
    MAT_METAL,
    MAT_MIRROR,
    MAT_NONE,
    MAT_PLASTIC,
    MAT_SUBSTRATE,
    MAT_TRANSLUCENT,
    MAT_UBER,
    MAT_FOURIER,
    MAT_HAIR,
    MAT_SUBSURFACE,
    ROUGH_GLASS_MIN,
)

_INV_PI = 1.0 / jnp.pi


# -------------------------------------------------------------------------
# Fresnel (reflection.cpp FrDielectric / FrConductor)
# -------------------------------------------------------------------------

def fresnel_dielectric(cos_i, eta_i, eta_t):
    """Unpolarized dielectric Fresnel; handles entering/exiting by sign."""
    cos_i = jnp.clip(cos_i, -1.0, 1.0)
    entering = cos_i > 0.0
    ei = jnp.where(entering, eta_i, eta_t)
    et = jnp.where(entering, eta_t, eta_i)
    ci = jnp.abs(cos_i)
    sin_t = ei / et * jnp.sqrt(jnp.maximum(0.0, 1.0 - ci * ci))
    tir = sin_t >= 1.0
    ct = jnp.sqrt(jnp.maximum(0.0, 1.0 - sin_t * sin_t))
    r_parl = (et * ci - ei * ct) / jnp.maximum(et * ci + ei * ct, 1e-20)
    r_perp = (ei * ci - et * ct) / jnp.maximum(ei * ci + et * ct, 1e-20)
    fr = 0.5 * (r_parl * r_parl + r_perp * r_perp)
    return jnp.where(tir, 1.0, fr)


def fresnel_conductor(cos_i, eta, k):
    """reflection.cpp FrConductor (per-channel; eta,k (...,3))."""
    ci = jnp.clip(jnp.abs(cos_i), 0.0, 1.0)[..., None]
    c2 = ci * ci
    s2 = 1.0 - c2
    e2 = eta * eta
    k2 = k * k
    t0 = e2 - k2 - s2
    a2b2 = jnp.sqrt(jnp.maximum(t0 * t0 + 4.0 * e2 * k2, 0.0))
    t1 = a2b2 + c2
    a = jnp.sqrt(jnp.maximum(0.5 * (a2b2 + t0), 0.0))
    t2 = 2.0 * a * ci
    rs = (t1 - t2) / jnp.maximum(t1 + t2, 1e-20)
    t3 = c2 * a2b2 + s2 * s2
    t4 = t2 * s2
    rp = rs * (t3 - t4) / jnp.maximum(t3 + t4, 1e-20)
    return 0.5 * (rp + rs)


# -------------------------------------------------------------------------
# Trowbridge-Reitz / GGX microfacet distribution (microfacet.cpp)
# -------------------------------------------------------------------------

# -------------------------------------------------------------------------
# Beckmann distribution (microfacet.cpp BeckmannDistribution) — D, Lambda,
# and full-distribution half-vector sampling (the non-visible-normal
# Sample_wh branch, exact for isotropic and anisotropic alphas).
# -------------------------------------------------------------------------

def beckmann_d(wh, ax, ay):
    t2 = tan2_theta(wh)
    c4 = cos2_theta(wh) ** 2
    e = jnp.exp(
        -t2 * (cos_phi(wh) ** 2 / jnp.maximum(ax * ax, 1e-12)
               + sin_phi(wh) ** 2 / jnp.maximum(ay * ay, 1e-12))
    )
    d = e / (jnp.pi * ax * ay * jnp.maximum(c4, 1e-16))
    return jnp.where(jnp.isfinite(t2) & (c4 > 1e-16), d, 0.0)


def beckmann_lambda(w, ax, ay):
    abs_tan = jnp.abs(tan_theta(w))
    alpha = jnp.sqrt(cos_phi(w) ** 2 * ax * ax + sin_phi(w) ** 2 * ay * ay)
    a = 1.0 / jnp.maximum(alpha * abs_tan, 1e-12)
    lam = (1.0 - 1.259 * a + 0.396 * a * a) / (3.535 * a + 2.181 * a * a)
    return jnp.where(jnp.isfinite(abs_tan) & (a < 1.6), lam, 0.0)


def beckmann_g(wo, wi, ax, ay):
    return 1.0 / (1.0 + beckmann_lambda(wo, ax, ay) + beckmann_lambda(wi, ax, ay))


def beckmann_sample_wh(u1, u2, ax, ay):
    """Full-distribution Beckmann Sample_wh (microfacet.cpp, the
    !sampleVisibleArea branch): tan2 = -a^2 log(1-u1) with per-phi alpha
    for the anisotropic case."""
    log_u = jnp.log(jnp.maximum(1.0 - u1, 1e-12))
    phi = jnp.arctan(ay / ax * jnp.tan(2.0 * jnp.pi * u2 + 0.5 * jnp.pi))
    phi = phi + jnp.where(u2 > 0.5, jnp.pi, 0.0)
    sp, cp = jnp.sin(phi), jnp.cos(phi)
    a2 = 1.0 / jnp.maximum(cp * cp / jnp.maximum(ax * ax, 1e-12)
                           + sp * sp / jnp.maximum(ay * ay, 1e-12), 1e-12)
    tan2 = -log_u * a2
    ct = 1.0 / jnp.sqrt(1.0 + tan2)
    st = jnp.sqrt(jnp.maximum(0.0, 1.0 - ct * ct))
    return jnp.stack([st * cp, st * sp, ct], axis=-1)


def beckmann_pdf(wh, ax, ay):
    """pdf of wh under full-distribution sampling: D(wh) |cos wh|."""
    return beckmann_d(wh, ax, ay) * abs_cos_theta(wh)


def tr_roughness_to_alpha(rough):
    """TrowbridgeReitzDistribution::RoughnessToAlpha."""
    rough = jnp.maximum(rough, 1e-3)
    x = jnp.log(rough)
    return (
        1.62142
        + 0.819955 * x
        + 0.1734 * x * x
        + 0.0171201 * x * x * x
        + 0.000640711 * x * x * x * x
    )


def tr_d(wh, ax, ay):
    t2 = tan2_theta(wh)
    c4 = cos2_theta(wh) ** 2
    e = (cos_phi(wh) ** 2 / jnp.maximum(ax * ax, 1e-12) + sin_phi(wh) ** 2 / jnp.maximum(ay * ay, 1e-12)) * t2
    d = 1.0 / (jnp.pi * ax * ay * c4 * (1.0 + e) ** 2)
    return jnp.where(jnp.isfinite(t2) & (c4 > 1e-16), d, 0.0)


def tr_lambda(w, ax, ay):
    abs_tan = jnp.abs(tan_theta(w))
    alpha = jnp.sqrt(cos_phi(w) ** 2 * ax * ax + sin_phi(w) ** 2 * ay * ay)
    a2t2 = (alpha * abs_tan) ** 2
    lam = (-1.0 + jnp.sqrt(1.0 + a2t2)) / 2.0
    return jnp.where(jnp.isfinite(abs_tan), lam, 0.0)


def tr_g(wo, wi, ax, ay):
    return 1.0 / (1.0 + tr_lambda(wo, ax, ay) + tr_lambda(wi, ax, ay))


def tr_g1(w, ax, ay):
    return 1.0 / (1.0 + tr_lambda(w, ax, ay))


def _tr_sample11(cos_t, u1, u2):
    """TrowbridgeReitzSample11: slopes for visible-normal sampling."""
    # special case: normal incidence
    sin_t = jnp.sqrt(jnp.maximum(0.0, 1.0 - cos_t * cos_t))
    tan_t = sin_t / jnp.maximum(cos_t, 1e-7)
    a = 1.0 / jnp.maximum(tan_t, 1e-12)
    g1 = 2.0 / (1.0 + jnp.sqrt(1.0 + 1.0 / jnp.maximum(a * a, 1e-20)))

    # pbrt TrowbridgeReitzSample11 verbatim: tmp = 1/(A^2-1) is NEGATIVE
    # for |A| < 1 and that sign is load-bearing — negating it (an earlier
    # "sanity" tweak) collapsed every u1 < 0.5 sample onto the horizon
    # (tr_d = 0), silently killing half of all VNDF samples
    A = 2.0 * u1 / jnp.maximum(g1, 1e-12) - 1.0
    denom = A * A - 1.0
    tmp = 1.0 / jnp.where(jnp.abs(denom) < 1e-12, jnp.where(denom < 0, -1e-12, 1e-12), denom)
    tmp = jnp.minimum(tmp, 1e10)
    B = tan_t
    D = jnp.sqrt(jnp.maximum(B * B * tmp * tmp - (A * A - B * B) * tmp, 0.0))
    slope_x_1 = B * tmp - D
    slope_x_2 = B * tmp + D
    slope_x = jnp.where((A < 0) | (slope_x_2 > 1.0 / jnp.maximum(tan_t, 1e-12)), slope_x_1, slope_x_2)

    S = jnp.where(u2 > 0.5, 1.0, -1.0)
    u2r = jnp.where(u2 > 0.5, 2.0 * (u2 - 0.5), 2.0 * (0.5 - u2))
    z = (u2r * (u2r * (u2r * 0.27385 - 0.73369) + 0.46341)) / (
        u2r * (u2r * (u2r * 0.093073 + 0.309420) - 1.000000) + 0.597999
    )
    slope_y = S * z * jnp.sqrt(1.0 + slope_x * slope_x)

    # normal incidence fallback
    r = jnp.sqrt(jnp.maximum(u1 / jnp.maximum(1.0 - u1, 1e-12), 0.0))
    phi = 6.28318530718 * u2
    ni = cos_t > 0.9999
    slope_x = jnp.where(ni, r * jnp.cos(phi), slope_x)
    slope_y = jnp.where(ni, r * jnp.sin(phi), slope_y)
    return slope_x, slope_y


def tr_sample_wh(wo, u1, u2, ax, ay):
    """Visible-normal sampling (TrowbridgeReitzDistribution::Sample_wh)."""
    flip = cos_theta(wo) < 0.0
    wo_f = jnp.where(flip[..., None], -wo, wo)
    # stretch
    wi_s = jnp.stack([ax * wo_f[..., 0], ay * wo_f[..., 1], wo_f[..., 2]], axis=-1)
    ln = jnp.sqrt(jnp.sum(wi_s * wi_s, axis=-1))
    wi_s = wi_s / jnp.maximum(ln[..., None], 1e-20)
    ct = jnp.clip(wi_s[..., 2], -1.0, 1.0)
    s_len = jnp.sqrt(jnp.maximum(0.0, 1.0 - ct * ct))
    cphi = jnp.where(s_len < 1e-7, 1.0, wi_s[..., 0] / jnp.maximum(s_len, 1e-12))
    sphi = jnp.where(s_len < 1e-7, 0.0, wi_s[..., 1] / jnp.maximum(s_len, 1e-12))
    sx, sy = _tr_sample11(ct, u1, u2)
    # rotate
    tmp = cphi * sx - sphi * sy
    sy = sphi * sx + cphi * sy
    sx = tmp
    # unstretch
    sx = sx * ax
    sy = sy * ay
    wh = jnp.stack([-sx, -sy, jnp.ones_like(sx)], axis=-1)
    wh = wh / jnp.sqrt(jnp.sum(wh * wh, axis=-1))[..., None]
    return jnp.where(flip[..., None], -wh, wh)


def tr_pdf(wo, wh, ax, ay):
    """pdf of wh under visible-normal sampling."""
    return (
        tr_d(wh, ax, ay)
        * tr_g1(wo, ax, ay)
        * jnp.abs(jnp.sum(wo * wh, axis=-1))
        / jnp.maximum(abs_cos_theta(wo), 1e-12)
    )


# -------------------------------------------------------------------------
# Material parameter gather
# -------------------------------------------------------------------------

class DisneyParams(NamedTuple):
    """Per-lane Disney 2015 parameters (disney.cpp); present only when
    the scene uses the material (dz field of MatParams is None
    otherwise, and none of the Disney code is traced)."""

    metallic: jnp.ndarray  # (R,)
    spectint: jnp.ndarray
    aniso: jnp.ndarray
    sheen: jnp.ndarray
    sheentint: jnp.ndarray
    clearcoat: jnp.ndarray
    ccgloss: jnp.ndarray
    strans: jnp.ndarray
    flat: jnp.ndarray
    dtrans: jnp.ndarray
    thin: jnp.ndarray  # (R,) bool
    rough: jnp.ndarray  # (R,) raw roughness (disney does NOT remap)


class MatParams(NamedTuple):
    mtype: jnp.ndarray  # (R,)
    kd: jnp.ndarray  # (R,3)
    ks: jnp.ndarray
    kr: jnp.ndarray
    kt: jnp.ndarray
    eta: jnp.ndarray  # (R,3)
    k: jnp.ndarray
    ax: jnp.ndarray  # (R,) GGX alphas (post-remap)
    ay: jnp.ndarray
    sigma: jnp.ndarray  # oren-nayar sigma (degrees)
    opacity: jnp.ndarray
    rough_raw: jnp.ndarray  # (R,) raw (pre-remap) roughness; 0 = smooth
    dz: "DisneyParams | None" = None
    hz: "HairParams | None" = None
    fz: "object | None" = None  # FourierTable (core/fourierbsdf.py)
    sub: "jnp.ndarray | None" = None  # (R,) BSSRDF table row; -1 = none


def resolve_mix(mat: dict, mid, u):
    """MixMaterial (mixmat.cpp) resolution: map a mix-material lane to
    ONE of its sub-material rows with probability `amount` before the
    parameter gather — the one-sample estimator of pbrt's scaled BSDF
    union (f = a*f1 + (1-a)*f2). Conditioned on the pick, the lane runs
    a standard path step under the sub-BSDF, so eval/sample/pdf and the
    MIS weights stay mutually consistent and the outer expectation over
    `u` reproduces the mix exactly (for scalar `amount`; colored
    amounts select by channel mean — warned at compile).

    Static no-op for mix-free scenes (the compiler only emits the
    mix_* columns when a mix exists). Nested mixes resolve through a
    static 4-level loop; `u` is rescaled within the picked branch so
    the levels stay independent."""
    if "mix_a" not in mat or u is None:
        return mid
    from tpu_pbrt.core.smalltab import small_take

    for _ in range(4):
        ma = small_take(mat["mix_a"], mid)
        mb = small_take(mat["mix_b"], mid)
        amt = small_take(mat["mix_amt"], mid)
        is_mix = ma >= 0
        pick_a = u < amt
        mid = jnp.where(is_mix & pick_a, ma, jnp.where(is_mix, mb, mid))
        u = jnp.clip(
            jnp.where(
                pick_a,
                u / jnp.maximum(amt, 1e-8),
                (u - amt) / jnp.maximum(1.0 - amt, 1e-8),
            ),
            0.0,
            0.9999999,
        )
    return mid


def gather_mat(mat: dict, mid) -> MatParams:
    from tpu_pbrt.core.smalltab import small_take

    mtype = small_take(mat["type"], mid)
    sub = None
    if "sub_id" in mat:
        # subsurface materials: the surface BSDF is EXACTLY smooth
        # glass (Fresnel reflect + transmit — subsurface.cpp's specular
        # interface), so lanes remap to MAT_GLASS here and the BSSRDF
        # transport is keyed off `sub` (integrators/path.py probe wave)
        sub = small_take(mat["sub_id"], mid)
        mtype = jnp.where(mtype == MAT_SUBSURFACE, MAT_GLASS, mtype)
    remap = small_take(mat["remap"], mid)
    ru = small_take(mat["rough_u"], mid)
    rv = small_take(mat["rough_v"], mid)
    ax = jnp.where(remap > 0, tr_roughness_to_alpha(ru), jnp.maximum(ru, 1e-3))
    ay = jnp.where(remap > 0, tr_roughness_to_alpha(rv), jnp.maximum(rv, 1e-3))
    return MatParams(
        mtype=mtype,
        kd=small_take(mat["kd"], mid),
        ks=small_take(mat["ks"], mid),
        kr=small_take(mat["kr"], mid),
        kt=small_take(mat["kt"], mid),
        eta=small_take(mat["eta"], mid),
        k=small_take(mat["k"], mid),
        ax=ax,
        ay=ay,
        sigma=small_take(mat["sigma"], mid),
        opacity=small_take(mat["opacity"], mid),
        # glass.cpp activates the microfacet lobes when EITHER axis is
        # rough (urough != 0 || vrough != 0)
        rough_raw=jnp.maximum(ru, rv),
        dz=DisneyParams(
            metallic=small_take(mat["d_metallic"], mid),
            spectint=small_take(mat["d_spectint"], mid),
            aniso=small_take(mat["d_aniso"], mid),
            sheen=small_take(mat["d_sheen"], mid),
            sheentint=small_take(mat["d_sheentint"], mid),
            clearcoat=small_take(mat["d_clearcoat"], mid),
            ccgloss=small_take(mat["d_ccgloss"], mid),
            strans=small_take(mat["d_strans"], mid),
            flat=small_take(mat["d_flat"], mid),
            dtrans=small_take(mat["d_dtrans"], mid),
            thin=small_take(mat["d_thin"], mid) > 0,
            rough=ru,
        ) if "d_metallic" in mat else None,
        hz=HairParams(
            sigma_a=small_take(mat["h_sigma_a"], mid),
            beta_m=small_take(mat["h_beta_m"], mid),
            beta_n=small_take(mat["h_beta_n"], mid),
            alpha=small_take(mat["h_alpha"], mid),
            h=jnp.zeros_like(small_take(mat["h_beta_m"], mid)),
        ) if "h_beta_m" in mat else None,
        fz=mat.get("_fourier"),
        sub=sub,
    )


def _lobe_flags(mp: MatParams):
    """(has_diffuse, has_glossy, is_specular_lobe, has_transmission)."""
    t = mp.mtype
    diffuse = (
        (t == MAT_MATTE)
        | (t == MAT_PLASTIC)
        | (t == MAT_UBER)
        | (t == MAT_TRANSLUCENT)
        | (t == MAT_DISNEY)
        | (t == MAT_HAIR)
        | (t == MAT_FOURIER)
        | (t == MAT_SUBSURFACE)
    )
    glossy = (
        (t == MAT_PLASTIC) | (t == MAT_METAL) | (t == MAT_UBER) | (t == MAT_SUBSTRATE) | (t == MAT_DISNEY)
        # rough glass is a real (non-delta) microfacet BSDF: SPPM stores
        # visible points on glossy surfaces at the depth cap, and
        # bsdf_eval/bsdf_sample override rg lanes wholesale, so flagging
        # it glossy here cannot double-count lobes
        | _is_rough_glass(mp)
    )
    specular = ((t == MAT_GLASS) & ~_is_rough_glass(mp)) | (t == MAT_MIRROR)
    return diffuse, glossy, specular


# -------------------------------------------------------------------------
# Lobe formulas (batched, local frame)
# -------------------------------------------------------------------------

def _diffuse_f(mp: MatParams, wo, wi):
    """Lambertian or Oren-Nayar by sigma; reflection hemisphere only."""
    refl = same_hemisphere(wo, wi)
    sigma = jnp.radians(mp.sigma)
    s2 = sigma * sigma
    a = 1.0 - s2 / (2.0 * (s2 + 0.33))
    b = 0.45 * s2 / (s2 + 0.09)
    sin_to = jnp.sqrt(sin2_theta(wo))
    sin_ti = jnp.sqrt(sin2_theta(wi))
    # max(0, cos(phi_i - phi_o))
    cos_dphi = cos_phi(wi) * cos_phi(wo) + sin_phi(wi) * sin_phi(wo)
    max_cos = jnp.maximum(0.0, cos_dphi)
    has_sin = (sin_to > 1e-4) & (sin_ti > 1e-4)
    max_cos = jnp.where(has_sin, max_cos, 0.0)
    abs_ci = abs_cos_theta(wi)
    abs_co = abs_cos_theta(wo)
    sin_alpha = jnp.where(abs_ci > abs_co, sin_to, sin_ti)
    tan_beta = jnp.where(
        abs_ci > abs_co,
        sin_ti / jnp.maximum(abs_ci, 1e-7),
        sin_to / jnp.maximum(abs_co, 1e-7),
    )
    on = a + b * max_cos * sin_alpha * tan_beta
    is_on = mp.sigma > 0.0
    base = jnp.where(is_on, on, 1.0)
    # translucent diffuse transmission: kd*kt on the opposite hemisphere
    trans_scale = jnp.where(
        (mp.mtype == MAT_TRANSLUCENT)[..., None], mp.kt, jnp.zeros_like(mp.kt)
    )
    refl_scale = jnp.where(
        (mp.mtype == MAT_TRANSLUCENT)[..., None], mp.kr, jnp.ones_like(mp.kr)
    )
    f_refl = mp.kd * (_INV_PI * base)[..., None] * refl_scale
    f_trans = mp.kd * _INV_PI * trans_scale
    return jnp.where(refl[..., None], f_refl, f_trans)


def _diffuse_pdf(mp: MatParams, wo, wi):
    refl = same_hemisphere(wo, wi)
    pdf_r = cosine_hemisphere_pdf(abs_cos_theta(wi))
    is_transl = mp.mtype == MAT_TRANSLUCENT
    # translucent splits the cosine pdf across both hemispheres
    return jnp.where(
        refl, jnp.where(is_transl, 0.5 * pdf_r, pdf_r), jnp.where(is_transl, 0.5 * pdf_r, 0.0)
    )


def _glossy_f(mp: MatParams, wo, wi):
    """Microfacet reflection lobe (or FresnelBlend for substrate)."""
    refl = same_hemisphere(wo, wi)
    wh = wi + wo
    wh_len = jnp.sqrt(jnp.sum(wh * wh, axis=-1))
    valid = refl & (wh_len > 1e-12) & (abs_cos_theta(wi) > 1e-7) & (abs_cos_theta(wo) > 1e-7)
    wh = wh / jnp.maximum(wh_len[..., None], 1e-20)
    d = tr_d(wh, mp.ax, mp.ay)
    g = tr_g(wo, wi, mp.ax, mp.ay)
    cos_wh = jnp.sum(wi * wh, axis=-1)
    is_metal = mp.mtype == MAT_METAL
    eta_s = mp.eta[..., 0]
    f_cond = fresnel_conductor(cos_wh, mp.eta, mp.k)
    f_diel = fresnel_dielectric(cos_wh, jnp.ones_like(eta_s), eta_s)[..., None]
    F = jnp.where(is_metal[..., None], f_cond, f_diel)
    scale = jnp.where(is_metal[..., None], jnp.ones_like(mp.ks), mp.ks)
    denom = 4.0 * abs_cos_theta(wi) * abs_cos_theta(wo)
    f_mf = scale * F * (d * g / jnp.maximum(denom, 1e-12))[..., None]

    # FresnelBlend (substrate): Ashikhmin-Shirley diffuse+spec
    is_sub = mp.mtype == MAT_SUBSTRATE
    pow5 = lambda v: (v * v) * (v * v) * v  # noqa: E731
    diff = (
        (28.0 / (23.0 * jnp.pi))
        * mp.kd
        * (1.0 - mp.ks)
        * (1.0 - pow5(1.0 - 0.5 * abs_cos_theta(wi)))[..., None]
        * (1.0 - pow5(1.0 - 0.5 * abs_cos_theta(wo)))[..., None]
    )
    schlick = mp.ks + pow5(1.0 - cos_wh)[..., None] * (1.0 - mp.ks)
    spec = (
        d
        / jnp.maximum(4.0 * jnp.abs(cos_wh) * jnp.maximum(abs_cos_theta(wi), abs_cos_theta(wo)), 1e-12)
    )[..., None] * schlick
    f_sub = diff + spec

    f = jnp.where(is_sub[..., None], f_sub, f_mf)
    return jnp.where(valid[..., None], f, 0.0)


def _glossy_pdf(mp: MatParams, wo, wi):
    refl = same_hemisphere(wo, wi)
    wh = wi + wo
    wh_len = jnp.sqrt(jnp.sum(wh * wh, axis=-1))
    wh = wh / jnp.maximum(wh_len[..., None], 1e-20)
    pdf_wh = tr_pdf(wo, wh, mp.ax, mp.ay)
    pdf = pdf_wh / jnp.maximum(4.0 * jnp.sum(wo * wh, axis=-1), 1e-12)
    is_sub = mp.mtype == MAT_SUBSTRATE
    # FresnelBlend pdf: average of cosine and half-vector pdfs
    pdf_sub = 0.5 * (cosine_hemisphere_pdf(abs_cos_theta(wi)) + pdf)
    pdf = jnp.where(is_sub, pdf_sub, pdf)
    return jnp.where(refl & (wh_len > 1e-12), pdf, 0.0)


def _is_rough_glass(mp: MatParams):
    return (mp.mtype == MAT_GLASS) & (mp.rough_raw > ROUGH_GLASS_MIN)


def _refract_about(wo, wh, eta_rel):
    """Refract wo about microfacet normal wh (faced toward wo);
    eta_rel = eta_incident / eta_transmitted. Returns (wi, tir)."""
    wh_f = jnp.where((jnp.sum(wo * wh, axis=-1) < 0.0)[..., None], -wh, wh)
    ci = jnp.sum(wo * wh_f, axis=-1)
    sin2t = eta_rel * eta_rel * jnp.maximum(0.0, 1.0 - ci * ci)
    tir = sin2t >= 1.0
    ctt = jnp.sqrt(jnp.maximum(0.0, 1.0 - sin2t))
    wi = eta_rel[..., None] * -wo + (eta_rel * ci - ctt)[..., None] * wh_f
    return wi, tir


def _mf_glass_terms(mp: MatParams, wo, wi, wh):
    """The MicrofacetReflection + MicrofacetTransmission formulas
    (reflection.cpp ::f/::Pdf) evaluated at an EXPLICIT half-vector —
    the single source both bsdf_eval (reconstructed whs) and bsdf_sample
    (the drawn wh) share, so the MIS pdfs cannot drift apart. wh is
    faceforwarded to +z internally (TIR via the signed Fresnel cosine).
    pdfs carry pbrt's uniform 2-lobe component weight (0.5 each).
    Radiance transport: transmission carries the 1/eta^2 scale.
    Returns (f_refl, pdf_refl, ok_refl, f_trans, pdf_trans, ok_trans)."""
    eta_s = mp.eta[..., 0]
    refl = same_hemisphere(wo, wi)
    ci = abs_cos_theta(wi)
    co = abs_cos_theta(wo)
    ok_angles = (ci > 1e-7) & (co > 1e-7)
    wh_z = jnp.where((wh[..., 2] < 0.0)[..., None], -wh, wh)
    do_h = jnp.sum(wo * wh_z, axis=-1)
    di_h = jnp.sum(wi * wh_z, axis=-1)
    d = tr_d(wh_z, mp.ax, mp.ay)
    g = tr_g(wo, wi, mp.ax, mp.ay)
    pdf_wh = tr_pdf(wo, wh_z, mp.ax, mp.ay)
    F = fresnel_dielectric(do_h, jnp.ones_like(eta_s), eta_s)

    f_refl = mp.kr * (d * g * F / jnp.maximum(4.0 * ci * co, 1e-12))[..., None]
    pdf_refl = 0.5 * pdf_wh / jnp.maximum(4.0 * jnp.abs(do_h), 1e-12)
    ok_refl = refl & ok_angles

    # eta = etaT/etaI of the transmitted side (MicrofacetTransmission)
    eta_t = jnp.where(cos_theta(wo) > 0.0, eta_s, 1.0 / jnp.maximum(eta_s, 1e-6))
    sqrt_denom = do_h + eta_t * di_h
    factor = 1.0 / jnp.maximum(eta_t, 1e-6)  # radiance transport scale
    f_trans = mp.kt * jnp.abs(
        d * g * eta_t * eta_t * (1.0 - F) * jnp.abs(di_h) * jnp.abs(do_h)
        * factor * factor
        / jnp.maximum(ci * co * sqrt_denom * sqrt_denom, 1e-12)
    )[..., None]
    dwh_dwi = jnp.abs(eta_t * eta_t * di_h) / jnp.maximum(
        sqrt_denom * sqrt_denom, 1e-12
    )
    pdf_trans = 0.5 * pdf_wh * dwh_dwi
    ok_trans = (~refl) & ok_angles & (do_h * di_h < 0.0)
    return f_refl, pdf_refl, ok_refl, f_trans, pdf_trans, ok_trans


def _rough_glass_f_pdf(mp: MatParams, wo, wi):
    """Eval path: reconstruct each lobe's half-vector from (wo, wi) —
    wo+wi for reflection, the generalized wo + eta*wi for transmission —
    then evaluate the shared terms at each."""
    eta_s = mp.eta[..., 0]
    wh_r = wi + wo
    whr_len = jnp.sqrt(jnp.sum(wh_r * wh_r, axis=-1))
    wh_rn = wh_r / jnp.maximum(whr_len[..., None], 1e-20)
    f_r, p_r, ok_r, _, _, _ = _mf_glass_terms(mp, wo, wi, wh_rn)
    ok_r = ok_r & (whr_len > 1e-12)

    eta_t = jnp.where(cos_theta(wo) > 0.0, eta_s, 1.0 / jnp.maximum(eta_s, 1e-6))
    wh_t = wo + wi * eta_t[..., None]
    wht_len = jnp.sqrt(jnp.sum(wh_t * wh_t, axis=-1))
    wh_tn = wh_t / jnp.maximum(wht_len[..., None], 1e-20)
    _, _, _, f_t, p_t, ok_t = _mf_glass_terms(mp, wo, wi, wh_tn)
    ok_t = ok_t & (wht_len > 1e-12)

    f = jnp.where(ok_r[..., None], f_r, 0.0) + jnp.where(ok_t[..., None], f_t, 0.0)
    pdf = jnp.where(ok_r, p_r, 0.0) + jnp.where(ok_t, p_t, 0.0)
    return f, pdf




# -------------------------------------------------------------------------
# Disney 2015 BSDF (materials/disney.cpp: DisneyDiffuse/FakeSS/Retro/
# Sheen, DisneyMicrofacetDistribution + DisneyFresnel, DisneyClearcoat,
# MicrofacetTransmission spec-trans, thin LambertianTransmission).
# Everything here is traced ONLY when the scene contains a disney
# material (MatParams.dz gating) — other scenes pay zero compile cost.
# -------------------------------------------------------------------------

def _sw(c):
    """SchlickWeight: (1-c)^5 clamped."""
    m = jnp.clip(1.0 - c, 0.0, 1.0)
    return (m * m) * (m * m) * m


def _gtr1_d(cos_h, alpha):
    a2 = alpha * alpha
    denom = jnp.pi * jnp.log(a2) * (1.0 + (a2 - 1.0) * cos_h * cos_h)
    return (a2 - 1.0) / jnp.where(jnp.abs(denom) < 1e-12, 1e-12, denom)


def _smith_g_sep(c, alpha):
    """Separable smith G1 with the clearcoat's fixed-alpha form
    (disney.cpp smithG_GGX)."""
    a2 = alpha * alpha
    c2 = c * c
    return 1.0 / (c + jnp.sqrt(jnp.maximum(a2 + c2 - a2 * c2, 1e-12)))


def _disney_weights(mp: MatParams):
    """Shared per-lane derived quantities."""
    from tpu_pbrt.core.spectrum import luminance

    dz = mp.dz
    c = mp.kd
    e = mp.eta[..., 0]
    metallic = dz.metallic
    strans = dz.strans
    dw = (1.0 - metallic) * (1.0 - strans)
    dt = dz.dtrans * 0.5
    lum = luminance(c)
    ctint = jnp.where((lum > 0.0)[..., None], c / jnp.maximum(lum, 1e-12)[..., None], 1.0)
    csheen = (1.0 - dz.sheentint)[..., None] + dz.sheentint[..., None] * ctint
    r0 = ((e - 1.0) / (e + 1.0)) ** 2
    cspec0 = (
        (1.0 - metallic)[..., None]
        * r0[..., None]
        * ((1.0 - dz.spectint)[..., None] + dz.spectint[..., None] * ctint)
        + metallic[..., None] * c
    )
    aspect = jnp.sqrt(jnp.maximum(1.0 - 0.9 * dz.aniso, 1e-6))
    r2 = dz.rough * dz.rough
    ax = jnp.maximum(1e-3, r2 / aspect)
    ay = jnp.maximum(1e-3, r2 * aspect)
    rscaled = (0.65 * e - 0.35) * dz.rough
    rs2 = rscaled * rscaled
    axt = jnp.where(dz.thin, jnp.maximum(1e-3, rs2 / aspect), ax)
    ayt = jnp.where(dz.thin, jnp.maximum(1e-3, rs2 * aspect), ay)
    gloss = 0.1 * (1.0 - dz.ccgloss) + 0.001 * dz.ccgloss
    return c, e, dw, dt, csheen, cspec0, ax, ay, axt, ayt, gloss


def _disney_presence(mp: MatParams):
    dz = mp.dz
    metallic = dz.metallic
    dw_pos = (1.0 - metallic) * (1.0 - dz.strans) > 0.0
    pr = [
        dw_pos,                      # 0 DisneyDiffuse
        dw_pos & dz.thin,            # 1 DisneyFakeSS
        dw_pos,                      # 2 DisneyRetro
        dw_pos & (dz.sheen > 0.0),   # 3 DisneySheen
        jnp.ones_like(dw_pos),       # 4 microfacet reflection
        dz.clearcoat > 0.0,          # 5 clearcoat
        dz.strans > 0.0,             # 6 microfacet spec transmission
        dz.thin,                     # 7 LambertianTransmission
    ]
    n = sum(p.astype(jnp.int32) for p in pr)
    return pr, n


def _disney_trans_terms(T, e, axt, ayt, wo, wi, wh):
    """MicrofacetTransmission::f/Pdf with Disney's separable G at an
    explicit half-vector (etaA=1, etaB=e, radiance transport)."""
    ci = abs_cos_theta(wi)
    co = abs_cos_theta(wo)
    ok = (ci > 1e-7) & (co > 1e-7) & ~same_hemisphere(wo, wi)
    eta_t = jnp.where(cos_theta(wo) > 0.0, e, 1.0 / jnp.maximum(e, 1e-6))
    wh_z = jnp.where((wh[..., 2] < 0.0)[..., None], -wh, wh)
    do_h = jnp.sum(wo * wh_z, axis=-1)
    di_h = jnp.sum(wi * wh_z, axis=-1)
    ok = ok & (do_h * di_h < 0.0)
    d = tr_d(wh_z, axt, ayt)
    g = tr_g1(wo, axt, ayt) * tr_g1(wi, axt, ayt)
    F = fresnel_dielectric(do_h, jnp.ones_like(e), e)
    sqrt_denom = do_h + eta_t * di_h
    factor = 1.0 / jnp.maximum(eta_t, 1e-6)
    f = T * jnp.abs(
        d * g * eta_t * eta_t * (1.0 - F) * jnp.abs(di_h) * jnp.abs(do_h)
        * factor * factor
        / jnp.maximum(ci * co * sqrt_denom * sqrt_denom, 1e-12)
    )[..., None]
    pdf_wh = tr_pdf(wo, wh_z, axt, ayt)
    dwh_dwi = jnp.abs(eta_t * eta_t * di_h) / jnp.maximum(
        sqrt_denom * sqrt_denom, 1e-12
    )
    pdf = pdf_wh * dwh_dwi
    return jnp.where(ok[..., None], f, 0.0), jnp.where(ok, pdf, 0.0), ok


def _disney_f_pdf(mp: MatParams, wo, wi):
    """f and per-lobe-averaged pdf over the full active lobe set
    (BSDF::f / BSDF::Pdf semantics over the Add()ed lobes)."""
    dz = mp.dz
    c, e, dw, dt, csheen, cspec0, ax, ay, axt, ayt, gloss = _disney_weights(mp)
    pr, n = _disney_presence(mp)
    refl = same_hemisphere(wo, wi)
    ci = abs_cos_theta(wi)
    co = abs_cos_theta(wo)
    ok_ang = (ci > 1e-7) & (co > 1e-7)

    wh = wi + wo
    wh_len = jnp.sqrt(jnp.sum(wh * wh, axis=-1))
    whn = wh / jnp.maximum(wh_len[..., None], 1e-20)
    cos_d = jnp.sum(wi * whn, axis=-1)  # cosThetaD
    FL = _sw(ci)
    FV = _sw(co)
    rough = dz.rough

    # 0: DisneyDiffuse
    f0 = (dw * (jnp.where(dz.thin, (1.0 - dz.flat) * (1.0 - dt), 1.0)))[
        ..., None
    ] * c * (_INV_PI * (1.0 - 0.5 * FL) * (1.0 - 0.5 * FV))[..., None]
    # 1: DisneyFakeSS
    fss90 = cos_d * cos_d * rough
    fss = (1.0 + (fss90 - 1.0) * FL) * (1.0 + (fss90 - 1.0) * FV)
    ss = 1.25 * (fss * (1.0 / jnp.maximum(ci + co, 1e-7) - 0.5) + 0.5)
    f1 = (dw * dz.flat * (1.0 - dt))[..., None] * c * (_INV_PI * ss)[..., None]
    # 2: DisneyRetro
    rr = 2.0 * rough * cos_d * cos_d
    f2 = dw[..., None] * c * (
        _INV_PI * rr * (FL + FV + FL * FV * (rr - 1.0))
    )[..., None]
    # 3: DisneySheen
    f3 = (dw * dz.sheen)[..., None] * csheen * _sw(cos_d)[..., None]
    # 4: microfacet reflection (GGX, Disney separable G + DisneyFresnel)
    d_mf = tr_d(whn, ax, ay)
    g_mf = tr_g1(wo, ax, ay) * tr_g1(wi, ax, ay)
    fr_diel = fresnel_dielectric(cos_d, jnp.ones_like(e), e)
    fr_schlick = cspec0 + _sw(cos_d)[..., None] * (1.0 - cspec0)
    F_mf = (1.0 - dz.metallic)[..., None] * fr_diel[..., None] + dz.metallic[
        ..., None
    ] * fr_schlick
    f4 = F_mf * (d_mf * g_mf / jnp.maximum(4.0 * ci * co, 1e-12))[..., None]
    # 5: clearcoat (GTR1)
    d_cc = _gtr1_d(jnp.abs(whn[..., 2]), gloss)
    f_cc = 0.04 + 0.96 * _sw(cos_d)
    g_cc = _smith_g_sep(ci, 0.25) * _smith_g_sep(co, 0.25)
    f5 = (0.25 * dz.clearcoat * d_cc * f_cc * g_cc)[..., None] * jnp.ones_like(c)

    refl_ok = (refl & ok_ang & (wh_len > 1e-12))[..., None]
    f_refl = (
        jnp.where(pr[0][..., None], f0, 0.0)
        + jnp.where(pr[1][..., None], f1, 0.0)
        + jnp.where(pr[2][..., None], f2, 0.0)
        + jnp.where(pr[3][..., None], f3, 0.0)
        + jnp.where(pr[4][..., None], f4, 0.0)
        + jnp.where(pr[5][..., None], f5, 0.0)
    )
    f = jnp.where(refl_ok, f_refl, 0.0)

    # 6: spec transmission (reconstruct the generalized half-vector)
    T6 = dz.strans[..., None] * jnp.sqrt(jnp.maximum(c, 0.0))
    eta_t = jnp.where(cos_theta(wo) > 0.0, e, 1.0 / jnp.maximum(e, 1e-6))
    wh_t = wo + wi * eta_t[..., None]
    wht_len = jnp.sqrt(jnp.sum(wh_t * wh_t, axis=-1))
    wh_tn = wh_t / jnp.maximum(wht_len[..., None], 1e-20)
    f6, p6, ok6 = _disney_trans_terms(T6, e, axt, ayt, wo, wi, wh_tn)
    ok6 = ok6 & (wht_len > 1e-12)
    f = f + jnp.where((pr[6] & ok6)[..., None], f6, 0.0)
    # 7: thin diffuse transmission
    f7 = (dt)[..., None] * c * _INV_PI
    f = f + jnp.where((pr[7] & ~refl & ok_ang)[..., None], f7, 0.0)

    # pdf: average over present lobes (cosine for 0-3, vndf for 4, GTR1
    # for 5, transmission jacobian for 6, flipped cosine for 7)
    pdf_cos = jnp.where(refl, cosine_hemisphere_pdf(ci), 0.0)
    n_cos = sum(p.astype(jnp.float32) for p in pr[0:4])
    pdf_mf = jnp.where(
        refl & (wh_len > 1e-12),
        tr_pdf(wo, whn, ax, ay)
        / jnp.maximum(4.0 * jnp.abs(jnp.sum(wo * whn, axis=-1)), 1e-12),
        0.0,
    )
    pdf_cc = jnp.where(
        refl & (wh_len > 1e-12),
        jnp.abs(d_cc * whn[..., 2])
        / jnp.maximum(4.0 * jnp.abs(jnp.sum(wo * whn, axis=-1)), 1e-12),
        0.0,
    )
    pdf_lt = jnp.where(~refl, cosine_hemisphere_pdf(ci), 0.0)
    pdf_sum = (
        n_cos * pdf_cos
        + jnp.where(pr[4], pdf_mf, 0.0)
        + jnp.where(pr[5], pdf_cc, 0.0)
        + jnp.where(pr[6] & ok6, p6, 0.0)
        + jnp.where(pr[7], pdf_lt, 0.0)
    )
    pdf = pdf_sum / jnp.maximum(n.astype(jnp.float32), 1.0)
    dead = ~ok_ang
    return jnp.where(dead[..., None], 0.0, f), jnp.where(dead, 0.0, pdf)


def _disney_sample_wi(mp: MatParams, wo, u_lobe, u1, u2):
    """Draw wi by picking uniformly among the PRESENT lobes (BSDF::
    Sample_f component choice); f/pdf then come from _disney_f_pdf."""
    dz = mp.dz
    c, e, dw, dt, csheen, cspec0, ax, ay, axt, ayt, gloss = _disney_weights(mp)
    pr, n = _disney_presence(mp)
    nf = n.astype(jnp.float32)
    k = jnp.minimum((u_lobe * nf).astype(jnp.int32), n - 1)
    # k-th present lobe: lobe j is chosen when cumsum(pr)[j]-1 == k
    cum = jnp.cumsum(jnp.stack([p.astype(jnp.int32) for p in pr]), axis=0)
    sel = [(cum[j] - 1 == k) & pr[j] for j in range(8)]

    sgn = jnp.where(cos_theta(wo) >= 0.0, 1.0, -1.0)
    # cosine candidates (lobes 0-3 same side, 7 flipped)
    wi_cos = cosine_sample_hemisphere(u1, u2)
    wi_cos = wi_cos * jnp.stack(
        [jnp.ones_like(sgn), jnp.ones_like(sgn), sgn], axis=-1
    )
    wi_lt = wi_cos * jnp.asarray([1.0, 1.0, -1.0])
    # microfacet reflection (vndf)
    wh_mf = tr_sample_wh(wo, u1, u2, ax, ay)
    wi_mf = -wo + 2.0 * jnp.sum(wo * wh_mf, axis=-1)[..., None] * wh_mf
    # clearcoat GTR1 half-vector (disney.cpp DisneyClearcoat::Sample_f)
    a2 = gloss * gloss
    ct_h = jnp.sqrt(
        jnp.maximum(0.0, (1.0 - jnp.power(a2, 1.0 - u1)) / (1.0 - a2))
    )
    st_h = jnp.sqrt(jnp.maximum(0.0, 1.0 - ct_h * ct_h))
    phi = 2.0 * jnp.pi * u2
    wh_cc = jnp.stack([st_h * jnp.cos(phi), st_h * jnp.sin(phi), ct_h], -1)
    wh_cc = jnp.where(same_hemisphere(wo, wh_cc)[..., None], wh_cc, -wh_cc)
    wi_cc = -wo + 2.0 * jnp.sum(wo * wh_cc, axis=-1)[..., None] * wh_cc
    # spec transmission: vndf on the (possibly thin-rescaled) dist
    wh_st = tr_sample_wh(wo, u1, u2, axt, ayt)
    eta_rel = jnp.where(
        cos_theta(wo) > 0.0, 1.0 / jnp.maximum(e, 1e-6), e
    )
    wi_st, tir_st = _refract_about(wo, wh_st, eta_rel)

    wi = wi_cos
    wi = jnp.where(sel[4][..., None], wi_mf, wi)
    wi = jnp.where(sel[5][..., None], wi_cc, wi)
    wi = jnp.where(sel[6][..., None], wi_st, wi)
    wi = jnp.where(sel[7][..., None], wi_lt, wi)
    ln = jnp.sqrt(jnp.sum(wi * wi, axis=-1))
    wi = wi / jnp.maximum(ln[..., None], 1e-20)
    bad = (sel[6] & tir_st) | (ln < 1e-12)
    return wi, bad




# -------------------------------------------------------------------------
# Hair BSDF (src/materials/hair.cpp, Chiang et al. 2016 "A Practical and
# Controllable Hair and Fur Model"): longitudinal Mp / azimuthal
# trimmed-logistic Np lobes for p = 0..3, dielectric attenuation Ap, and
# the 2-degree scale-tilt recurrences. The local frame follows pbrt's
# curve convention: x along the curve tangent, (y, z) the azimuthal
# plane; h in [-1, 1] is the across-width offset (-1 + 2 * uv.v for the
# tessellated flat ribbons). Traced only when a scene uses hair
# (MatParams.hz gating).
# -------------------------------------------------------------------------

_H_PMAX = 3
_SQRT_PI_OVER_8 = 0.626657069


class HairParams(NamedTuple):
    sigma_a: jnp.ndarray  # (R,3)
    beta_m: jnp.ndarray  # (R,)
    beta_n: jnp.ndarray
    alpha: jnp.ndarray  # degrees
    h: jnp.ndarray  # (R,) across-width offset, set from uv at shade time


def _safe_sqrt(x):
    return jnp.sqrt(jnp.maximum(x, 0.0))


def _safe_asin(x):
    return jnp.arcsin(jnp.clip(x, -1.0, 1.0))


def _i0(x):
    """Modified Bessel I0, 10-term series (hair.cpp I0)."""
    val = jnp.zeros_like(x)
    x2i = jnp.ones_like(x)
    ifact = 1.0
    i4 = 1.0
    for i in range(10):
        if i > 1:
            ifact *= i
        val = val + x2i / (i4 * ifact * ifact)
        x2i = x2i * x * x
        i4 *= 4.0
    return val


def _log_i0(x):
    big = x > 12.0
    lb = x + 0.5 * (-jnp.log(2.0 * jnp.pi) + jnp.log(1.0 / jnp.maximum(x, 1e-12)) + 1.0 / (8.0 * jnp.maximum(x, 1e-12)))
    ls = jnp.log(jnp.maximum(_i0(jnp.minimum(x, 12.0)), 1e-38))
    return jnp.where(big, lb, ls)


def _mp(cos_ti, cos_to, sin_ti, sin_to, v):
    a = cos_ti * cos_to / v
    b = sin_ti * sin_to / v
    small = v <= 0.1
    m_small = jnp.exp(
        _log_i0(a) - b - 1.0 / v + 0.6931 + jnp.log(1.0 / (2.0 * v))
    )
    vb = jnp.maximum(v, 0.05)  # keep the big-v branch finite under where
    m_big = (
        jnp.exp(-jnp.minimum(b, 80.0)) * _i0(jnp.minimum(a, 12.0))
    ) / (jnp.sinh(jnp.minimum(1.0 / vb, 80.0)) * 2.0 * vb)
    return jnp.where(small, m_small, m_big)


def _logistic(x, s):
    x = jnp.abs(x)
    e = jnp.exp(-x / s)
    return e / (s * (1.0 + e) ** 2)


def _logistic_cdf(x, s):
    return 1.0 / (1.0 + jnp.exp(-x / s))


def _trimmed_logistic(x, s):
    pi = jnp.pi
    norm = _logistic_cdf(pi, s) - _logistic_cdf(-pi, s)
    return _logistic(x, s) / jnp.maximum(norm, 1e-12)


def _sample_trimmed_logistic(u, s):
    pi = jnp.pi
    k = _logistic_cdf(pi, s) - _logistic_cdf(-pi, s)
    x = -s * jnp.log(
        1.0 / jnp.maximum(u * k + _logistic_cdf(-pi, s), 1e-12) - 1.0
    )
    return jnp.clip(x, -pi, pi)


def _hair_phi_p(p, gamma_o, gamma_t):
    return 2.0 * p * gamma_t - 2.0 * gamma_o + p * jnp.pi


def _wrap_pi(x):
    return jnp.mod(x + jnp.pi, 2.0 * jnp.pi) - jnp.pi


def _hair_setup(mp: MatParams, wo):
    """Shared per-lane terms (hair.cpp f()/Pdf() prologue)."""
    hz = mp.hz
    eta = mp.eta[..., 0]
    h = hz.h
    bm = hz.beta_m
    bn = hz.beta_n
    v0 = (0.726 * bm + 0.812 * bm * bm + 3.7 * bm ** 20) ** 2
    vs = [v0, 0.25 * v0, 4.0 * v0, 4.0 * v0]
    s = _SQRT_PI_OVER_8 * (0.265 * bn + 1.194 * bn * bn + 5.372 * bn ** 22)
    a_rad = jnp.radians(hz.alpha)
    sin2k = [jnp.sin(a_rad)]
    cos2k = [_safe_sqrt(1.0 - sin2k[0] ** 2)]
    for i in range(1, 3):
        sin2k.append(2.0 * cos2k[i - 1] * sin2k[i - 1])
        cos2k.append(cos2k[i - 1] ** 2 - sin2k[i - 1] ** 2)

    sin_to = wo[..., 0]
    cos_to = _safe_sqrt(1.0 - sin_to * sin_to)
    phi_o = jnp.arctan2(wo[..., 2], wo[..., 1])
    sin_tt = sin_to / eta
    cos_tt = _safe_sqrt(1.0 - sin_tt * sin_tt)
    etap = _safe_sqrt(eta * eta - sin_to * sin_to) / jnp.maximum(cos_to, 1e-6)
    sin_gt = h / jnp.maximum(etap, 1e-6)
    cos_gt = _safe_sqrt(1.0 - sin_gt * sin_gt)
    gamma_t = _safe_asin(sin_gt)
    gamma_o = _safe_asin(h)
    # transmittance of one internal segment
    T = jnp.exp(
        -hz.sigma_a * (2.0 * cos_gt / jnp.maximum(cos_tt, 1e-6))[..., None]
    )
    # attenuation Ap (hair.cpp Ap())
    cos_go = _safe_sqrt(1.0 - h * h)
    fr = fresnel_dielectric(cos_to * cos_go, jnp.ones_like(eta), eta)[..., None]
    ap0 = jnp.broadcast_to(fr, T.shape)
    ap1 = (1.0 - fr) ** 2 * T
    ap2 = ap1 * T * fr
    ap3 = ap2 * fr * T / jnp.maximum(1.0 - T * fr, 1e-4)
    aps = [ap0, ap1, ap2, ap3]

    # tilted longitudinal angles per p (hair.cpp "account for scales")
    tilts = []
    for p in range(3):
        if p == 0:
            st = sin_to * cos2k[1] - cos_to * sin2k[1]
            ct = cos_to * cos2k[1] + sin_to * sin2k[1]
        elif p == 1:
            st = sin_to * cos2k[0] + cos_to * sin2k[0]
            ct = cos_to * cos2k[0] - sin_to * sin2k[0]
        else:
            st = sin_to * cos2k[2] + cos_to * sin2k[2]
            ct = cos_to * cos2k[2] - sin_to * sin2k[2]
        tilts.append((st, jnp.abs(ct)))
    tilts.append((sin_to, cos_to))

    from tpu_pbrt.core.spectrum import luminance

    ap_lum = [luminance(a) for a in aps]
    tot = sum(ap_lum)
    ap_pdf = [al / jnp.maximum(tot, 1e-12) for al in ap_lum]
    return (eta, s, vs, gamma_o, gamma_t, phi_o, sin_to, cos_to, aps,
            ap_pdf, tilts)


def _hair_f_pdf(mp: MatParams, wo, wi):
    """HairBSDF::f and ::Pdf."""
    (eta, s, vs, gamma_o, gamma_t, phi_o, sin_to, cos_to, aps, ap_pdf,
     tilts) = _hair_setup(mp, wo)
    sin_ti = wi[..., 0]
    cos_ti = _safe_sqrt(1.0 - sin_ti * sin_ti)
    phi_i = jnp.arctan2(wi[..., 2], wi[..., 1])
    phi = phi_i - phi_o
    fsum = jnp.zeros_like(mp.kd)
    pdf = jnp.zeros_like(sin_to)
    for p in range(_H_PMAX):
        st, ct = tilts[p]
        m = _mp(cos_ti, ct, sin_ti, st, vs[p])
        n = _trimmed_logistic(
            _wrap_pi(phi - _hair_phi_p(p, gamma_o, gamma_t)), s
        )
        fsum = fsum + aps[p] * (m * n)[..., None]
        pdf = pdf + ap_pdf[p] * m * n
    st, ct = tilts[_H_PMAX]
    m_last = _mp(cos_ti, ct, sin_ti, st, vs[_H_PMAX])
    inv2pi = 1.0 / (2.0 * jnp.pi)
    fsum = fsum + aps[_H_PMAX] * (m_last * inv2pi)[..., None]
    pdf = pdf + ap_pdf[_H_PMAX] * m_last * inv2pi
    f = fsum / jnp.maximum(abs_cos_theta(wi), 1e-6)[..., None]
    ok = jnp.isfinite(pdf) & jnp.all(jnp.isfinite(f), axis=-1)
    return jnp.where(ok[..., None], f, 0.0), jnp.where(ok, pdf, 0.0)


def _hair_sample_wi(mp: MatParams, wo, u_lobe, u1, u2):
    """HairBSDF::Sample_f direction draw: pick p by the attenuation
    pdf, sample Mp longitudinally and the trimmed logistic azimuthally.
    u_lobe is consumed for the p choice and its remainder reused for
    the azimuthal sample (pbrt demuxes one sample the same way)."""
    (eta, s, vs, gamma_o, gamma_t, phi_o, sin_to, cos_to, aps, ap_pdf,
     tilts) = _hair_setup(mp, wo)
    c0 = ap_pdf[0]
    c1 = c0 + ap_pdf[1]
    c2 = c1 + ap_pdf[2]
    p_idx = (
        (u_lobe >= c0).astype(jnp.int32)
        + (u_lobe >= c1).astype(jnp.int32)
        + (u_lobe >= c2).astype(jnp.int32)
    )
    prev = jnp.where(
        p_idx == 0, 0.0,
        jnp.where(p_idx == 1, c0, jnp.where(p_idx == 2, c1, c2)),
    )
    width = jnp.where(
        p_idx == 0, c0,
        jnp.where(
            p_idx == 1, c1 - c0, jnp.where(p_idx == 2, c2 - c1, 1.0 - c2)
        ),
    )
    u_np = jnp.clip((u_lobe - prev) / jnp.maximum(width, 1e-9), 0.0, 0.9999)

    def sel(vals):
        out = vals[0]
        for p in range(1, 4):
            out = jnp.where(p_idx == p, vals[p], out)
        return out

    v_p = sel(vs)
    st_p = sel([t[0] for t in tilts])
    ct_p = sel([t[1] for t in tilts])
    u1c = jnp.maximum(u1, 1e-5)
    cos_theta = 1.0 + v_p * jnp.log(
        u1c
        + (1.0 - u1c)
        * jnp.exp(-jnp.minimum(2.0 / jnp.maximum(v_p, 1e-6), 80.0))
    )
    sin_theta = _safe_sqrt(1.0 - cos_theta * cos_theta)
    cos_phi_s = jnp.cos(2.0 * jnp.pi * u2)
    sin_ti = -cos_theta * st_p + sin_theta * cos_phi_s * ct_p
    cos_ti = _safe_sqrt(1.0 - sin_ti * sin_ti)
    dphi_smooth = sel(
        [_hair_phi_p(p, gamma_o, gamma_t) for p in range(4)]
    ) + _sample_trimmed_logistic(u_np, s)
    dphi = jnp.where(p_idx < _H_PMAX, dphi_smooth, 2.0 * jnp.pi * u_np)
    phi_i = phi_o + dphi
    wi = jnp.stack(
        [sin_ti, cos_ti * jnp.cos(phi_i), cos_ti * jnp.sin(phi_i)], axis=-1
    )
    return wi


# -------------------------------------------------------------------------
# Public API
# -------------------------------------------------------------------------

def bsdf_eval(mp: MatParams, wo, wi):
    """f(wo,wi) and pdf for non-specular lobes (pbrt BSDF::f / BSDF::Pdf
    with BSDF_ALL & ~SPECULAR: specular lobes contribute zero)."""
    has_d, has_g, is_spec = _lobe_flags(mp)
    f = jnp.zeros_like(mp.kd)
    pdf = jnp.zeros_like(mp.ax)
    fd = _diffuse_f(mp, wo, wi)
    pd = _diffuse_pdf(mp, wo, wi)
    fg = _glossy_f(mp, wo, wi)
    pg = _glossy_pdf(mp, wo, wi)
    f = jnp.where(has_d[..., None], fd, 0.0) + jnp.where(has_g[..., None], fg, 0.0)
    n_lobes = has_d.astype(jnp.float32) + has_g.astype(jnp.float32)
    pdf = (jnp.where(has_d, pd, 0.0) + jnp.where(has_g, pg, 0.0)) / jnp.maximum(n_lobes, 1.0)
    # rough (microfacet) glass is a real non-delta BSDF (glass.cpp)
    rg = _is_rough_glass(mp)
    f_rg, pdf_rg = _rough_glass_f_pdf(mp, wo, wi)
    f = jnp.where(rg[..., None], f_rg, f)
    pdf = jnp.where(rg, pdf_rg, pdf)
    if mp.dz is not None:
        dzl = mp.mtype == MAT_DISNEY
        f_dz, pdf_dz = _disney_f_pdf(mp, wo, wi)
        f = jnp.where(dzl[..., None], f_dz, f)
        pdf = jnp.where(dzl, pdf_dz, pdf)
    if mp.hz is not None:
        hl = mp.mtype == MAT_HAIR
        f_h, pdf_h = _hair_f_pdf(mp, wo, wi)
        f = jnp.where(hl[..., None], f_h, f)
        pdf = jnp.where(hl, pdf_h, pdf)
    if mp.fz is not None:
        from tpu_pbrt.core.fourierbsdf import fourier_f_pdf

        fl = mp.mtype == MAT_FOURIER
        f_fo, pdf_fo = fourier_f_pdf(mp.fz, wo, wi)
        f = jnp.where(fl[..., None], f_fo, f)
        pdf = jnp.where(fl, pdf_fo, pdf)
    dead = (is_spec & ~rg) | (mp.mtype == MAT_NONE)
    return jnp.where(dead[..., None], 0.0, f), jnp.where(dead, 0.0, pdf)


class BSDFSample(NamedTuple):
    wi: jnp.ndarray  # (R,3) local frame
    f: jnp.ndarray  # (R,3)
    pdf: jnp.ndarray  # (R,)
    is_specular: jnp.ndarray  # (R,) bool
    is_transmission: jnp.ndarray  # (R,) bool


def bsdf_sample(mp: MatParams, wo, u_lobe, u1, u2) -> BSDFSample:
    """BSDF::Sample_f over the batch. u_lobe picks among matching lobes
    (pbrt's uniform component choice); u1,u2 drive the chosen lobe."""
    has_d, has_g, is_spec = _lobe_flags(mp)
    n_lobes = has_d.astype(jnp.int32) + has_g.astype(jnp.int32)
    pick_g = has_g & ((~has_d) | (u_lobe * n_lobes.astype(jnp.float32) >= 1.0))

    # --- diffuse candidate (cosine hemisphere) ---------------------------
    # translucent: u2's low bit picks reflect/transmit, then u2 is remapped
    # to [0,1) so the decision and the disk coordinate are independent —
    # reusing raw u2 for both would cover only half the transmitted disk
    # while _diffuse_pdf claims the full hemisphere (ADVICE r1)
    is_transl = mp.mtype == MAT_TRANSLUCENT
    flip_t = is_transl & (u2 < 0.5)
    u2d = jnp.where(is_transl, jnp.where(u2 < 0.5, 2.0 * u2, 2.0 * (u2 - 0.5)), u2)
    wi_d = cosine_sample_hemisphere(u1, u2d)
    wi_d = jnp.where((cos_theta(wo) < 0.0)[..., None], wi_d * jnp.asarray([1.0, 1.0, -1.0]), wi_d)
    wi_d = jnp.where(flip_t[..., None], wi_d * jnp.asarray([1.0, 1.0, -1.0]), wi_d)

    # --- glossy candidate (VNDF half-vector) -----------------------------
    wh = tr_sample_wh(wo, u1, u2, mp.ax, mp.ay)
    wi_g = -wo + 2.0 * jnp.sum(wo * wh, axis=-1)[..., None] * wh
    # substrate: half the samples are cosine (FresnelBlend::Sample_f)
    is_sub = mp.mtype == MAT_SUBSTRATE
    use_cos = is_sub & (u_lobe < 0.5)
    wi_g = jnp.where(use_cos[..., None], wi_d, wi_g)

    wi = jnp.where(pick_g[..., None], wi_g, wi_d)

    dz_bad = None
    if mp.dz is not None:
        dzl = mp.mtype == MAT_DISNEY
        wi_dz, bad_dz = _disney_sample_wi(mp, wo, u_lobe, u1, u2)
        wi = jnp.where(dzl[..., None], wi_dz, wi)
        dz_bad = dzl & bad_dz
    if mp.hz is not None:
        hl = mp.mtype == MAT_HAIR
        wi_h = _hair_sample_wi(mp, wo, u_lobe, u1, u2)
        wi = jnp.where(hl[..., None], wi_h, wi)
    if mp.fz is not None:
        from tpu_pbrt.core.fourierbsdf import fourier_sample_wi

        fl = mp.mtype == MAT_FOURIER
        wi_fo = fourier_sample_wi(wo, u_lobe, u1, u2)
        wi = jnp.where(fl[..., None], wi_fo, wi)

    # --- combined f/pdf over matching non-specular lobes -----------------
    f_ns, pdf_ns = bsdf_eval(mp, wo, wi)

    # --- specular materials ---------------------------------------------
    eta_s = mp.eta[..., 0]
    ct_o = cos_theta(wo)
    F = fresnel_dielectric(ct_o, jnp.ones_like(eta_s), eta_s)
    is_glass = mp.mtype == MAT_GLASS
    is_mirror = mp.mtype == MAT_MIRROR
    # mirror: perfect reflection, FresnelNoOp
    wi_mirror = jnp.stack([-wo[..., 0], -wo[..., 1], wo[..., 2]], axis=-1)
    f_mirror = mp.kr / jnp.maximum(abs_cos_theta(wi_mirror), 1e-12)[..., None]
    # glass: choose R/T by Fresnel using u_lobe
    reflect_g = u_lobe < F
    entering = ct_o > 0.0
    ei = jnp.where(entering, 1.0, eta_s)
    et = jnp.where(entering, eta_s, 1.0)
    eta_rel = ei / et
    # refract in local frame about +/- z
    n_loc = jnp.stack(
        [jnp.zeros_like(ct_o), jnp.zeros_like(ct_o), jnp.where(entering, 1.0, -1.0)], axis=-1
    )
    ci = jnp.abs(ct_o)
    sin2_t = eta_rel * eta_rel * jnp.maximum(0.0, 1.0 - ci * ci)
    ct_t = jnp.sqrt(jnp.maximum(0.0, 1.0 - sin2_t))
    wi_refr = eta_rel[..., None] * -wo + (eta_rel * ci - ct_t)[..., None] * n_loc
    f_refl_g = (F / jnp.maximum(abs_cos_theta(wi_mirror), 1e-12))[..., None] * mp.kr
    # radiance transport: (ei/et)^2 factor
    f_trans_g = (
        ((1.0 - F) * (ei / et) ** 2 / jnp.maximum(jnp.abs(ct_t), 1e-12))[..., None] * mp.kt
    )
    wi_glass = jnp.where(reflect_g[..., None], wi_mirror, wi_refr)
    f_glass = jnp.where(reflect_g[..., None], f_refl_g, f_trans_g)
    pdf_glass = jnp.where(reflect_g, F, 1.0 - F)

    wi = jnp.where(is_mirror[..., None], wi_mirror, wi)
    wi = jnp.where(is_glass[..., None], wi_glass, wi)
    f = jnp.where(is_mirror[..., None], f_mirror, f_ns)
    f = jnp.where(is_glass[..., None], f_glass, f)
    pdf = jnp.where(is_mirror, 1.0, pdf_ns)
    pdf = jnp.where(is_glass, pdf_glass, pdf)

    # --- rough (microfacet) glass: override the delta-glass pick ---------
    # f/pdf come from the SAMPLED half-vector (pbrt Microfacet*::Sample_f
    # computes its pdf from the wh it drew) — reconstructing wh from wi
    # breaks down in f32 for the near-saturated slopes sample11 emits at
    # high alpha (identical degenerate whs -> D = 0 -> dropped samples)
    rg = _is_rough_glass(mp)
    wh_rg = tr_sample_wh(wo, u1, u2, mp.ax, mp.ay)
    refl_pick = u_lobe < 0.5  # pbrt BSDF uniform 2-lobe component choice
    wi_rg_r = -wo + 2.0 * jnp.sum(wo * wh_rg, axis=-1)[..., None] * wh_rg
    ct_o_rg = cos_theta(wo)
    eta_rel_rg = jnp.where(ct_o_rg > 0.0, 1.0 / jnp.maximum(eta_s, 1e-6), eta_s)
    wi_rg_t, tir_rg = _refract_about(wo, wh_rg, eta_rel_rg)
    wi_rg = jnp.where(refl_pick[..., None], wi_rg_r, wi_rg_t)

    f_r, p_r, ok_r2, f_t, p_t, ok_t2 = _mf_glass_terms(mp, wo, wi_rg, wh_rg)
    ok_rg = jnp.where(refl_pick, ok_r2, ok_t2 & ~tir_rg)
    f_rg = jnp.where(refl_pick[..., None], f_r, f_t)
    pdf_rg = jnp.where(refl_pick, p_r, p_t)
    wi = jnp.where(rg[..., None], wi_rg, wi)
    f = jnp.where((rg & ok_rg)[..., None], f_rg, jnp.where(rg[..., None], 0.0, f))
    pdf = jnp.where(rg, jnp.where(ok_rg, pdf_rg, 0.0), pdf)

    is_specular = (is_glass & ~rg) | is_mirror
    is_transmission = (is_glass & ~rg & ~reflect_g) | (flip_t & ~pick_g) | (
        rg & ~same_hemisphere(wo, wi)
    )
    if dz_bad is not None:
        dzl = mp.mtype == MAT_DISNEY
        pdf = jnp.where(dz_bad, 0.0, pdf)
        is_transmission = jnp.where(
            dzl, ~same_hemisphere(wo, wi), is_transmission
        )
    if mp.hz is not None:
        # hair has no radiance-scaling transmission; leave eta_scale alone
        is_transmission = jnp.where(
            mp.mtype == MAT_HAIR, jnp.zeros_like(is_transmission),
            is_transmission,
        )
    if mp.fz is not None:
        # the two-sided fourier sampler crosses hemispheres: medium
        # interfaces must switch exactly as for any transmitted ray
        is_transmission = jnp.where(
            mp.mtype == MAT_FOURIER, ~same_hemisphere(wo, wi),
            is_transmission,
        )
    dead = (mp.mtype == MAT_NONE) | (pdf <= 0.0)
    f = jnp.where(dead[..., None], 0.0, f)
    pdf = jnp.where(dead, 0.0, pdf)
    return BSDFSample(wi, f, pdf, is_specular, is_transmission)
