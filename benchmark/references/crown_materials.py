"""The plain reference of `crown-materials`: a brute-force path tracer over
the scene description of `descriptions/crown_materials.py`, with matte,
metal and glass surfaces under one environment map.

It shares none of the program's machinery: no acceleration structure (every
ray is tested against every triangle, Moller-Trumbore, the test copied from
`reference.py`), no wavefront pool, no sampler tables (jax.random), no
multiple importance sampling and no Russian roulette. It imports nothing of
`tpu_pbrt`, reads the description alone, and computes element-wise in
float32 (`dtype=` / `intersect_dtype=` lower the whole of it, or its
ray-triangle test alone, as `reference.py` defines them; the map's CDFs, a
lookup table, stay float32 as `reference.py`'s emitter CDF does).

Its estimator counts each vertex's direct light exactly once, and without
MIS (no two estimates of one light are weighed against each other):
  - at a MATTE vertex it samples the map (its own two-level CDF over the
    texels, built in numpy from luminance x sin(theta) mixed with a tenth
    of sin(theta) alone, searched with `jnp.searchsorted`; the pdf is the
    two CDF steps it landed in) and casts a shadow ray; the cosine-sampled
    continuation that escapes adds nothing;
  - at a METAL vertex it draws ONE direction from the mixture, half and
    half, of the BSDF's density and the map's: a Trowbridge-Reitz
    half-vector from D(wh) cos(theta_h) (pbrt-v3's full-distribution
    `Sample_wh`, not the program's visible-normal one), or the map's own
    CDF as at a matte vertex; the path goes on along it with f cos over
    the mixture's pdf (the conductor Fresnel), and the continuation that
    escapes adds Le times that. A mixture is one estimator with one
    density: the map's bright sun seen in a glossy lobe, which the BSDF
    alone finds about once in a hundred draws, is drawn half the time;
  - at a GLASS vertex it takes the reflection or the refraction that the
    dielectric Fresnel chooses; the continuation that escapes adds Le;
  - a camera ray that escapes adds Le.
So a path traces maxdepth + 1 rays: the ray that leaves the last vertex
counts only if it escapes.

pbrt-v3 conventions it follows:
  - the map (infinite.cpp): a direction w goes to light space by the inverse
    of the `Rotate` before the LightSource; phi = atan2(y, x) in [0, 2 pi),
    theta = acos(z), (s, t) = (phi / 2 pi, theta / pi), a bilinear lookup at
    level 0 with texel centres at (x + 0.5) / W (MIPMap::Bilerp), repeating
    in phi. Departure: theta is clamped at the poles where pbrt's MIPMap
    repeats it too (half a texel row at each pole, 1e-6 of the sphere);
  - `remaproughness` true: TrowbridgeReitzDistribution::RoughnessToAlpha;
  - the shading frame of a triangle without uv (triangle.cpp): dpdu from the
    default uvs (0,0), (1,0), (1,1), which is p1 - p0, made orthogonal to
    the shading normal (Gram-Schmidt); an anisotropic lobe's u axis lies
    along it (alpha_x with uroughness);
  - FrConductor with eta and k per channel at |wi . wh|; FrDielectric and the
    (eta_i / eta_t)^2 radiance scale of a refraction (FresnelSpecular);
  - the geometric normal flipped to the shading normal's side; a matte
    vertex's reflection decided against the geometric normal, its cosine
    taken with the shading normal (BSDF::f), as `reference.py` does.
Departure, as `reference.py`: ray origins are offset along the geometric
normal by a fixed relative epsilon.
"""

from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np


def _harness_reference():
    """`reference.py` of the benchmark's directory, for its helpers."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "reference.py")
    spec = importlib.util.spec_from_file_location("bench_reference_for_crown_materials", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_REF = _harness_reference()
_camera, _dot, _cross, _normalize = _REF._camera, _REF._dot, _REF._cross, _REF._normalize

TRI_BLOCK = 8192
_EPS = 1e-4
MATTE, METAL, GLASS = 0, 1, 2
#: pbrt's Y (luminance) weights of linear RGB
_Y = np.asarray([0.212671, 0.715160, 0.072169])


def roughness_to_alpha(r: float) -> float:
    """TrowbridgeReitzDistribution::RoughnessToAlpha (pbrt-v3)."""
    x = math.log(max(float(r), 1e-3))
    return 1.62142 + 0.819955 * x + 0.1734 * x**2 + 0.0171201 * x**3 + 0.000640711 * x**4


def rotation(deg: float, axis) -> np.ndarray:
    """pbrt's Rotate(theta, axis) as a 3x3 matrix (light to world)."""
    a = np.asarray(axis, np.float64) / np.linalg.norm(axis)
    s, c = math.sin(math.radians(deg)), math.cos(math.radians(deg))
    x, y, z = a
    return np.asarray([
        [x * x + (1 - x * x) * c, x * y * (1 - c) - z * s, x * z * (1 - c) + y * s],
        [x * y * (1 - c) + z * s, y * y + (1 - y * y) * c, y * z * (1 - c) - x * s],
        [x * z * (1 - c) - y * s, y * z * (1 - c) + x * s, z * z + (1 - z * z) * c],
    ])


def material_table(desc: dict):
    """-> (per-mesh row index, table of rows): type, kd, eta, k, alpha_x,
    alpha_y, glass eta, Kr, Kt (numpy, float64)."""
    rows, ids = [], []
    for m in desc["meshes"]:
        mat = m["material"]
        row = {"type": MATTE, "kd": np.zeros(3), "eta": np.ones(3), "k": np.zeros(3), "ax": 1.0, "ay": 1.0,
               "ior": 1.0, "kr": np.zeros(3), "kt": np.zeros(3)}
        if mat["type"] == "matte":
            row["kd"] = np.asarray(mat["Kd"], np.float64)
        elif mat["type"] == "metal":
            ur = mat.get("uroughness", mat.get("roughness"))
            vr = mat.get("vroughness", mat.get("roughness"))
            row.update(type=METAL, eta=np.asarray(mat["eta"], np.float64), k=np.asarray(mat["k"], np.float64),
                       ax=roughness_to_alpha(ur), ay=roughness_to_alpha(vr))
        elif mat["type"] == "glass":
            row.update(type=GLASS, ior=float(mat["eta"]), kr=np.asarray(mat["Kr"], np.float64),
                       kt=np.asarray(mat["Kt"], np.float64))
        else:
            raise ValueError(f"no material {mat['type']!r} in this reference")
        ids.append(len(rows))
        rows.append(row)
    table = {k: np.asarray([r[k] for r in rows], np.float64) for k in rows[0]}
    table["type"] = table["type"].astype(np.int32)
    return ids, table


def flatten(desc: dict) -> dict:
    """All meshes as one triangle soup (numpy): corners, per-corner shading
    normals (the geometric normal where a mesh has none) and a material row
    a triangle; padded with far degenerate triangles to TRI_BLOCK."""
    ids, table = material_table(desc)
    v0, v1, v2, n0, n1, n2, mid = ([] for _ in range(7))
    for m, i in zip(desc["meshes"], ids):
        P = np.asarray(m["P"], np.float32).astype(np.float64)
        F = np.asarray(m["indices"], np.int64)
        a, b, c = P[F[:, 0]], P[F[:, 1]], P[F[:, 2]]
        if m.get("N") is not None:
            N = np.asarray(m["N"], np.float32).astype(np.float64)
            na, nb, nc = N[F[:, 0]], N[F[:, 1]], N[F[:, 2]]
        else:
            ng = np.cross(b - a, c - a)
            na = nb = nc = ng / np.maximum(np.linalg.norm(ng, axis=-1, keepdims=True), 1e-30)
        for lst, x in zip((v0, v1, v2, n0, n1, n2), (a, b, c, na, nb, nc)):
            lst.append(x)
        mid.append(np.full(len(F), i, np.int32))
    cat = lambda xs: np.concatenate(xs, axis=0)  # noqa: E731
    v0, v1, v2, n0, n1, n2, mid = map(cat, (v0, v1, v2, n0, n1, n2, mid))
    T = len(v0)
    pad = (-T) % TRI_BLOCK if T > TRI_BLOCK else 0

    def padded(x, fill=0.0):
        return np.concatenate([x, np.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0) if pad else x

    return {"v0": padded(v0, 1e30), "e1": padded(v1 - v0), "e2": padded(v2 - v0),
            "n0": padded(n0), "n1": padded(n1), "n2": padded(n2), "mid": padded(mid),
            "table": table}


def env_tables(env: dict) -> dict:
    """The map and this reference's own sampling tables: a marginal CDF over
    rows (H+1) and a conditional CDF a row (H, W+1), float32, from the
    texels' luminance x sin(theta) mixed with a tenth of sin(theta) alone
    (so every texel a bilinear lookup can reach is sampled), and the
    light-to-world rotation."""
    img = np.asarray(env["map"], np.float64)
    h, w = img.shape[:2]
    sin_t = np.sin((np.arange(h) + 0.5) / h * math.pi)[:, None]
    lum = np.maximum(img @ _Y, 0.0) * sin_t
    area = np.broadcast_to(sin_t, (h, w))
    f = 0.9 * lum / lum.sum() + 0.1 * area / area.sum()
    rows = f.sum(axis=1)
    marg = np.concatenate([[0.0], np.cumsum(rows)]) / rows.sum()
    cond = np.concatenate([np.zeros((h, 1)), np.cumsum(f, axis=1)], axis=1) / rows[:, None]
    marg[-1], cond[:, -1] = 1.0, 1.0
    return {"map": img, "marg": marg.astype(np.float32), "cond": cond.astype(np.float32),
            "l2w": rotation(env["rotate"][0], env["rotate"][1:])}


def make_renderer(desc: dict, ray_block: int, dtype=jnp.float32, intersect_dtype=None):
    """-> render(pix_xy (N,2) int32, spp, seed) -> (N,3) float64 mean radiance.
    N * spp must be a multiple of ray_block."""
    fs = flatten(desc)
    et = env_tables(desc["env"])
    np_dtype = np.dtype(dtype)  # ml_dtypes gives numpy a bfloat16
    idtype = dtype if intersect_dtype is None else intersect_dtype
    T = fs["v0"].shape[0]
    nb = max(T // TRI_BLOCK, 1)
    tb = T // nb
    eye, right, new_up, fwd, xres, yres, tx, ty = _camera(desc)
    env_h, env_w = et["map"].shape[:2]
    # Everything the program below reads is prepared on the host and handed
    # to it as ARGUMENTS: no eager device op and no scene baked into the
    # program as a constant.
    A = {k: fs[k].astype(np_dtype) for k in ("v0", "e1", "e2", "n0", "n1", "n2")}
    A["mid"] = fs["mid"]
    for k in ("v0", "e1", "e2"):
        # (nb, 3, tb): triangles on the minor axis, one row per component
        A[k + "b"] = np.ascontiguousarray(
            np.transpose(fs[k].astype(np.dtype(idtype)).reshape(nb, tb, 3), (0, 2, 1)))
    A["mat"] = {k: (v if k == "type" else v.astype(np_dtype)) for k, v in fs["table"].items()}
    A.update(
        env_map=et["map"].astype(np_dtype), env_marg=et["marg"], env_cond=et["cond"],
        l2w=et["l2w"].astype(np_dtype), w2l=et["l2w"].T.copy().astype(np_dtype),
        eye=eye.astype(np_dtype), right=right.astype(np_dtype),
        new_up=new_up.astype(np_dtype), fwd=fwd.astype(np_dtype),
    )
    maxdepth = int(desc["maxdepth"])
    inf = float("inf")
    two_pi2 = 2.0 * math.pi * math.pi

    def intersect(A, o, d, t_max):
        """Closest hit of each ray over all triangles -> (t, tri) with
        t = inf on a miss. o, d: (R,3); t_max: (R,). (reference.py's test.)"""
        o, d, t_max = o.astype(idtype), d.astype(idtype), t_max.astype(idtype)
        ox, oy, oz = (o[:, i : i + 1] for i in range(3))
        dx, dy, dz = (d[:, i : i + 1] for i in range(3))

        def step(carry, tri):
            t_best, i_best, base = carry
            a, b, c = tri  # each (3, tb)
            px = dy * c[2] - dz * c[1]
            py = dz * c[0] - dx * c[2]
            pz = dx * c[1] - dy * c[0]
            det = b[0] * px + b[1] * py + b[2] * pz
            inv = 1.0 / det
            sx_, sy_, sz_ = ox - a[0], oy - a[1], oz - a[2]
            u = (sx_ * px + sy_ * py + sz_ * pz) * inv
            qx = sy_ * b[2] - sz_ * b[1]
            qy = sz_ * b[0] - sx_ * b[2]
            qz = sx_ * b[1] - sy_ * b[0]
            v = (dx * qx + dy * qy + dz * qz) * inv
            t = (c[0] * qx + c[1] * qy + c[2] * qz) * inv
            ok = (det != 0) & (u >= 0) & (v >= 0) & (u + v <= 1) & (t > 0) & (t < t_max[:, None])
            t = jnp.where(ok, t, inf)
            tb_min = jnp.min(t, axis=1)
            ib = jnp.argmin(t, axis=1).astype(jnp.int32) + base
            better = tb_min < t_best
            return (jnp.where(better, tb_min, t_best), jnp.where(better, ib, i_best), base + tb), None

        R = o.shape[0]
        init = (jnp.full((R,), inf, idtype), jnp.full((R,), -1, jnp.int32), jnp.int32(0))
        (t, i, _), _ = jax.lax.scan(step, init, (A["v0b"], A["e1b"], A["e2b"]))
        return t.astype(dtype), i

    def offset(p, ng, w):
        eps = (_EPS * jnp.maximum(1.0, jnp.max(jnp.abs(p), axis=-1))).astype(dtype)
        sign = jnp.where(_dot(ng, w) >= 0, 1.0, -1.0).astype(dtype)
        return p + (sign * eps)[:, None] * ng

    def env_le(A, w):
        """The map's radiance toward world direction w (bilinear, level 0)."""
        wl = _normalize(w[:, 0:1] * A["w2l"][:, 0] + w[:, 1:2] * A["w2l"][:, 1] + w[:, 2:3] * A["w2l"][:, 2])
        phi = jnp.arctan2(wl[:, 1], wl[:, 0])
        phi = jnp.where(phi < 0, phi + 2.0 * math.pi, phi)
        theta = jnp.arccos(jnp.clip(wl[:, 2], -1.0, 1.0))
        x = phi * (env_w / (2.0 * math.pi)) - 0.5
        y = theta * (env_h / math.pi) - 0.5
        x0, y0 = jnp.floor(x), jnp.floor(y)
        fx, fy = (x - x0)[:, None], (y - y0)[:, None]
        xi0 = jnp.mod(x0.astype(jnp.int32), env_w)
        xi1 = jnp.mod(x0.astype(jnp.int32) + 1, env_w)
        yi0 = jnp.clip(y0.astype(jnp.int32), 0, env_h - 1)
        yi1 = jnp.clip(y0.astype(jnp.int32) + 1, 0, env_h - 1)
        m = A["env_map"]
        return ((1 - fx) * (1 - fy) * m[yi0, xi0] + fx * (1 - fy) * m[yi0, xi1]
                + (1 - fx) * fy * m[yi1, xi0] + fx * fy * m[yi1, xi1])

    def env_sample(A, u0, u1, u2):
        """A direction from this reference's CDFs: the row of u0, the column
        of u1 in it, uniform within the texel -> (world wi, solid-angle pdf:
        the two CDF steps it landed in over the texel's solid angle)."""
        marg, cond = A["env_marg"], A["env_cond"]
        row = jnp.clip(jnp.searchsorted(marg, u0.astype(jnp.float32), side="right") - 1, 0, env_h - 1)
        crow = cond[row]  # (R, W+1): this reference's plain row gather
        col = jax.vmap(lambda c, x: jnp.searchsorted(c, x, side="right"))(crow, u1.astype(jnp.float32)) - 1
        col = jnp.clip(col, 0, env_w - 1)
        at = lambda j: jnp.take_along_axis(crow, j[:, None], axis=1)[:, 0]  # noqa: E731
        c0, c1 = at(col), at(col + 1)
        p_texel = (marg[row + 1] - marg[row]) * (c1 - c0)
        # within the texel: u1's place between the column's CDF steps, u2 in the row
        fu = jnp.clip((u1.astype(jnp.float32) - c0) / jnp.maximum(c1 - c0, 1e-30), 0.0, 1.0)
        theta = ((row.astype(dtype) + u2) * (math.pi / env_h)).astype(dtype)
        phi = ((col.astype(dtype) + fu.astype(dtype)) * (2.0 * math.pi / env_w)).astype(dtype)
        sin_t = jnp.sin(theta)
        wl = jnp.stack([sin_t * jnp.cos(phi), sin_t * jnp.sin(phi), jnp.cos(theta)], axis=-1)
        wi = _normalize(wl[:, 0:1] * A["l2w"][:, 0] + wl[:, 1:2] * A["l2w"][:, 1] + wl[:, 2:3] * A["l2w"][:, 2])
        pdf = p_texel.astype(dtype) * (env_h * env_w / two_pi2) / jnp.maximum(sin_t, 1e-20)
        return wi, jnp.where(sin_t > 0, pdf, 0.0).astype(dtype)

    def fr_dielectric(cos_i, eta):
        """FrDielectric(cos_i, 1, eta) -> (F, entering, eta_i / eta_t, cos_t)."""
        cos_i = jnp.clip(cos_i, -1.0, 1.0)
        entering = cos_i > 0
        ei = jnp.where(entering, 1.0, eta)
        et_ = jnp.where(entering, eta, 1.0)
        ci = jnp.abs(cos_i)
        sin_t = ei / et_ * jnp.sqrt(jnp.maximum(0.0, 1.0 - ci * ci))
        cos_t = jnp.sqrt(jnp.maximum(0.0, 1.0 - sin_t * sin_t))
        r_parl = (et_ * ci - ei * cos_t) / (et_ * ci + ei * cos_t)
        r_perp = (ei * ci - et_ * cos_t) / (ei * ci + et_ * cos_t)
        F = jnp.where(sin_t >= 1.0, 1.0, 0.5 * (r_parl * r_parl + r_perp * r_perp))
        return F, entering, ei / et_, cos_t

    def fr_conductor(cos_i, eta, k):
        """FrConductor(|cos_i|, 1, eta, k), per channel: (R,) x (R,3) -> (R,3)."""
        c = jnp.clip(jnp.abs(cos_i), 0.0, 1.0)[:, None]
        c2 = c * c
        s2 = 1.0 - c2
        e2, k2 = eta * eta, k * k
        t0 = e2 - k2 - s2
        a2b2 = jnp.sqrt(jnp.maximum(t0 * t0 + 4.0 * e2 * k2, 0.0))
        t1 = a2b2 + c2
        a = jnp.sqrt(jnp.maximum(0.5 * (a2b2 + t0), 0.0))
        t2 = 2.0 * c * a
        rs = (t1 - t2) / (t1 + t2)
        t3 = c2 * a2b2 + s2 * s2
        t4 = t2 * s2
        rp = rs * (t3 - t4) / (t3 + t4)
        return 0.5 * (rp + rs)

    def ggx_lambda(w, ax, ay):
        """Smith's Lambda of Trowbridge-Reitz for local direction w."""
        xy2 = w[:, 0] * w[:, 0] + w[:, 1] * w[:, 1]
        z2 = jnp.maximum(w[:, 2] * w[:, 2], 1e-20)
        alpha2 = jnp.where(xy2 > 0, (w[:, 0] ** 2 * ax * ax + w[:, 1] ** 2 * ay * ay) / jnp.maximum(xy2, 1e-20),
                           ax * ay)
        return 0.5 * (-1.0 + jnp.sqrt(1.0 + alpha2 * xy2 / z2))

    def env_pdf(A, w):
        """The solid-angle pdf with which `env_sample` draws world direction
        w: its texel's two CDF steps over the texel's solid angle."""
        wl = _normalize(w[:, 0:1] * A["w2l"][:, 0] + w[:, 1:2] * A["w2l"][:, 1] + w[:, 2:3] * A["w2l"][:, 2])
        phi = jnp.arctan2(wl[:, 1], wl[:, 0])
        phi = jnp.where(phi < 0, phi + 2.0 * math.pi, phi)
        theta = jnp.arccos(jnp.clip(wl[:, 2], -1.0, 1.0))
        row = jnp.clip((theta * (env_h / math.pi)).astype(jnp.int32), 0, env_h - 1)
        col = jnp.clip((phi * (env_w / (2.0 * math.pi))).astype(jnp.int32), 0, env_w - 1)
        marg, cond = A["env_marg"], A["env_cond"]
        p_texel = (marg[row + 1] - marg[row]) * (cond[row, col + 1] - cond[row, col])
        sin_t = jnp.sin(theta)
        pdf = p_texel.astype(dtype) * (env_h * env_w / two_pi2) / jnp.maximum(sin_t, 1e-20)
        return jnp.where(sin_t > 0, pdf, 0.0).astype(dtype)

    def metal_wh(u0, u1, ax, ay):
        """A half-vector from D(wh) cos(theta_h), on the +z side."""
        phi_a = jnp.arctan(ay / ax * jnp.tan(2.0 * math.pi * u1 + 0.5 * math.pi))
        phi = jnp.where(u1 > 0.5, phi_a + math.pi, phi_a)
        cp, sp = jnp.cos(phi), jnp.sin(phi)
        alpha2 = 1.0 / (cp * cp / (ax * ax) + sp * sp / (ay * ay))
        tan2 = alpha2 * u0 / jnp.maximum(1.0 - u0, 1e-7)
        cos_h = 1.0 / jnp.sqrt(1.0 + tan2)
        sin_h = jnp.sqrt(jnp.maximum(0.0, 1.0 - cos_h * cos_h))
        return jnp.stack([sin_h * cp, sin_h * sp, cos_h], axis=-1)

    def metal_eval(wo, wi, ax, ay, eta, k):
        """At local wo and wi -> (f cos_i, the pdf with which `metal_wh`
        draws wi, ok): f = D G F / (4 cos_i cos_o), the pdf
        D(wh) cos(theta_h) / (4 |wo . wh|)."""
        ok = (wi[:, 2] * wo[:, 2] > 0) & (jnp.abs(wo[:, 2]) > 0)
        wh = wo + wi
        wh = _normalize(jnp.where((wh[:, 2] < 0)[:, None], -wh, wh))
        cos2 = jnp.maximum(wh[:, 2] * wh[:, 2], 1e-20)
        sin2 = jnp.maximum(1.0 - cos2, 0.0)
        xy2 = jnp.maximum(wh[:, 0] * wh[:, 0] + wh[:, 1] * wh[:, 1], 1e-20)
        e = (wh[:, 0] * wh[:, 0] / (ax * ax) + wh[:, 1] * wh[:, 1] / (ay * ay)) / xy2 * (sin2 / cos2)
        D = 1.0 / (math.pi * ax * ay * cos2 * cos2 * (1.0 + e) ** 2)
        o_h = jnp.abs(_dot(wo, wh))
        ok = ok & (o_h > 0)
        G = 1.0 / (1.0 + ggx_lambda(wo, ax, ay) + ggx_lambda(wi, ax, ay))
        F = fr_conductor(o_h, eta, k)
        fcos = F * (D * G / (4.0 * jnp.maximum(jnp.abs(wo[:, 2]), 1e-20)))[:, None]
        pdf = D * wh[:, 2] / (4.0 * jnp.maximum(o_h, 1e-20))
        return fcos, pdf, ok

    def paths(key, pix, A):
        """One block of R paths -> (R,3) radiance. A: the scene's arrays."""
        v0, e1, e2, n0, n1, n2 = (A[k] for k in ("v0", "e1", "e2", "n0", "n1", "n2"))
        mat = A["mat"]
        R = pix.shape[0]
        u = jax.random.uniform(key, (R, 2 + 6 * maxdepth), jnp.float32).astype(dtype)
        fx = pix[:, 0].astype(dtype) + u[:, 0]
        fy = pix[:, 1].astype(dtype) + u[:, 1]
        cx = ((2.0 * fx / xres - 1.0) * tx).astype(dtype)
        cy = ((1.0 - 2.0 * fy / yres) * ty).astype(dtype)
        d = _normalize(cx[:, None] * A["right"] + cy[:, None] * A["new_up"] + A["fwd"])
        o = jnp.broadcast_to(A["eye"], d.shape)
        L = jnp.zeros((R, 3), dtype)
        beta = jnp.ones((R, 3), dtype)
        alive = jnp.ones((R,), bool)
        counts = jnp.ones((R,), bool)  # an escape adds Le: camera ray, or after metal / glass
        for depth in range(maxdepth + 1):
            t, tri = intersect(A, o, d, jnp.where(alive, inf, -1.0).astype(dtype))
            hit = alive & (tri >= 0)
            esc = alive & (tri < 0)
            L = L + jnp.where((esc & counts)[:, None], beta * env_le(A, d), 0.0)
            if depth == maxdepth:
                break
            uu = u[:, 2 + 6 * depth : 8 + 6 * depth]
            tri = jnp.maximum(tri, 0)
            a, b, c = v0[tri], e1[tri], e2[tri]
            p = o + jnp.where(hit, t, 0.0)[:, None] * d
            ng = _normalize(_cross(b, c))
            pv = _cross(d, c)
            inv = 1.0 / jnp.where(hit, _dot(b, pv), 1.0)
            s = o - a
            bu = _dot(s, pv) * inv
            bv = _dot(d, _cross(s, b)) * inv
            ns = _normalize((1.0 - bu - bv)[:, None] * n0[tri] + bu[:, None] * n1[tri] + bv[:, None] * n2[tri])
            ng = jnp.where((_dot(ng, ns) < 0)[:, None], -ng, ng)
            wo = -d
            m = A["mid"][tri]
            mtype = mat["type"][m]
            matte, metal, glass = hit & (mtype == MATTE), hit & (mtype == METAL), hit & (mtype == GLASS)

            # ---- matte: the map sampled, a shadow ray; f = Kd / pi
            kd = mat["kd"][m]
            wi_l, pdf_l = env_sample(A, uu[:, 0], uu[:, 1], uu[:, 2])
            lit = matte & (_dot(wi_l, ng) * _dot(wo, ng) > 0) & (pdf_l > 0)
            ts, _ = intersect(A, offset(p, ng, wi_l), wi_l, jnp.where(lit, inf, -1.0).astype(dtype))
            vis = lit & ~(ts < inf)
            fcos = (jnp.abs(_dot(wi_l, ns)) * (1.0 / math.pi) / jnp.maximum(pdf_l, 1e-30)).astype(dtype)
            L = L + jnp.where(vis[:, None], beta * kd * env_le(A, wi_l) * fcos[:, None], 0.0)
            # continuation: cosine about the shading normal on wo's side (f cos / pdf = Kd)
            r = jnp.sqrt(uu[:, 3])
            ph = ((2.0 * math.pi) * uu[:, 4]).astype(dtype)
            lx, ly = r * jnp.cos(ph), r * jnp.sin(ph)
            lz = jnp.sqrt(jnp.maximum(1.0 - uu[:, 3], 0.0))
            nz = jnp.where((_dot(wo, ns) < 0)[:, None], -ns, ns)
            helper = jnp.where((jnp.abs(nz[:, 0]) > 0.9)[:, None],
                               jnp.asarray([0.0, 1.0, 0.0], dtype), jnp.asarray([1.0, 0.0, 0.0], dtype))
            sx_ = _normalize(_cross(helper, nz))
            sy_ = _cross(nz, sx_)
            wi_m = _normalize(lx[:, None] * sx_ + ly[:, None] * sy_ + lz[:, None] * nz)
            ok_m = matte & (_dot(wi_m, ng) * _dot(wo, ng) > 0)

            # ---- metal: one direction from the mixture of the BSDF's
            # density and the map's, in pbrt's dpdu frame; uu[5] picks
            xs = b - ns * _dot(b, ns)[:, None]  # dpdu = p1 - p0, Gram-Schmidt against ns
            xs = _normalize(xs)
            ys = _cross(ns, xs)
            to_loc = lambda w: jnp.stack([_dot(w, xs), _dot(w, ys), _dot(w, ns)], axis=-1)  # noqa: E731
            wo_loc = to_loc(wo)
            ax, ay = mat["ax"][m], mat["ay"][m]
            wh = metal_wh(uu[:, 3], uu[:, 4], ax, ay)
            wh = jnp.where((wo_loc[:, 2] < 0)[:, None], -wh, wh)
            wi_b = -wo_loc + (2.0 * _dot(wo_loc, wh))[:, None] * wh
            by_map = uu[:, 5] < 0.5
            wi_t = jnp.where(by_map[:, None], wi_l,
                             _normalize(wi_b[:, 0:1] * xs + wi_b[:, 1:2] * ys + wi_b[:, 2:3] * ns))
            fcos_t, pdf_b, ok_t = metal_eval(wo_loc, to_loc(wi_t), ax, ay, mat["eta"][m], mat["k"][m])
            pdf_mix = 0.5 * pdf_b + 0.5 * jnp.where(by_map, pdf_l, env_pdf(A, wi_t))
            ok_t = metal & ok_t & (pdf_mix > 0)
            w_metal = fcos_t / jnp.maximum(pdf_mix, 1e-30)[:, None]

            # ---- glass: reflection or refraction as the Fresnel term chooses
            cos_o = _dot(wo, ns)
            F, entering, eta_rel, cos_tr = fr_dielectric(cos_o, mat["ior"][m])
            refl = uu[:, 5] < F
            wi_r = -wo + (2.0 * cos_o)[:, None] * ns
            nf = jnp.where(entering[:, None], ns, -ns)
            wi_tr = _normalize(-eta_rel[:, None] * wo + (eta_rel * jnp.abs(cos_o) - cos_tr)[:, None] * nf)
            wi_g = jnp.where(refl[:, None], wi_r, wi_tr)
            w_glass = jnp.where(refl[:, None], mat["kr"][m], mat["kt"][m] * (eta_rel * eta_rel)[:, None])

            wi = jnp.where(matte[:, None], wi_m, jnp.where(metal[:, None], wi_t, wi_g))
            scale = jnp.where(matte[:, None], kd, jnp.where(metal[:, None], w_metal, w_glass))
            alive = ok_m | ok_t | glass
            beta = jnp.where(alive[:, None], beta * scale, beta)
            counts = metal | glass
            o = offset(p, ng, wi)
            d = wi
        return L.astype(jnp.float32)

    @jax.jit
    def render_blocks(seed, pix_blocks, A):
        keys = jax.random.split(jax.random.key(seed), pix_blocks.shape[0])
        return jax.lax.map(lambda a: paths(a[0], a[1], A), (keys, pix_blocks))

    def render(pix_xy, spp: int, seed: int):
        pix = np.repeat(np.asarray(pix_xy, np.int32), spp, axis=0)
        n = len(pix)
        if n % ray_block:
            raise ValueError(f"{n} paths are not a multiple of the ray block {ray_block}")
        out = render_blocks(
            np.uint32(int(seed) % (2**32)), pix.reshape(n // ray_block, ray_block, 2), A
        )
        out = np.asarray(jax.device_get(out), np.float64).reshape(len(pix_xy), spp, 3)
        return out.mean(axis=1)

    return render
