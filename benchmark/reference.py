"""The plain reference: a brute-force path tracer over a scene description.

It renders the same light transport that the configuration states (paths of
up to `maxdepth` scattering vertices, direct light at each, matte surfaces,
one-sided diffuse area lights, point lights, a pinhole perspective camera, a
box filter) with none of the program's machinery: no acceleration structure
(every ray is tested against every triangle, Moller-Trumbore), no wavefront
pool, no compaction, no sampler tables (jax.random), no multiple importance
sampling (light sampling alone estimates the direct light, every light
sampled once at every vertex, so a ray that the surface scatters onto a light
adds nothing, and only a camera ray sees a light's own radiance), no Russian
roulette. Its expected value is the one
pbrt's PathIntegrator has for the same scene.

It imports nothing of the program and reads the scene description alone
(`scenedesc.py`): not the `.pbrt` file, not the PLY, nothing the program has
parsed, built or packed. Arithmetic is element-wise float32 (no matrix
product, so the TPU's reduced-precision matmul passes do not enter);
`dtype=bfloat16` is the control of "How `correct` is decided": the same
code, every array and every operation one precision lower.
`intersect_dtype=bfloat16` is a second, narrower control: the ray-triangle
test alone one precision lower (the step that would tempt a later PR, a
one-pass matrix product in the tracer), everything else in float32.

Follows pbrt-v3 where the two differ in convention: the geometric normal of
a triangle is flipped to the interpolated shading normal's side; reflection
is decided against the geometric normal, the cosine taken with the shading
normal; a diffuse area light emits on the side of cross(p1-p0, p2-p0).
Departure: ray origins are offset along the geometric normal by a fixed
relative epsilon, where pbrt uses its error bounds.

It is the reference of every configuration whose file names none. One whose
scene this reference cannot render (another material, another light) names
its own under "reference": `references/<name>.py`, found by `run.py`'s
`config_modules`. Such a module keeps this one's contract: it imports nothing
of `tpu_pbrt` and reads the scene description alone; it computes in float32
element-wise, or under `jax.default_matmul_precision("highest")` wherever it
multiplies matrices; `make_renderer(desc, ray_block, dtype=..., intersect_dtype=...)`
returns `render(pix_xy, spp, seed) -> (N, 3)` mean radiance, `dtype` and
`intersect_dtype` lowering the whole of it or its ray-triangle test alone as
above (a module without either keyword is refused when it is loaded). It may
import this module's helpers (`flatten_scene`, `_camera`) or copy what it needs.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

TRI_BLOCK = 8192
_EPS = 1e-4


def _dot(a, b):
    return a[..., 0] * b[..., 0] + a[..., 1] * b[..., 1] + a[..., 2] * b[..., 2]


def _cross(a, b):
    return jnp.stack(
        [
            a[..., 1] * b[..., 2] - a[..., 2] * b[..., 1],
            a[..., 2] * b[..., 0] - a[..., 0] * b[..., 2],
            a[..., 0] * b[..., 1] - a[..., 1] * b[..., 0],
        ],
        axis=-1,
    )


def _normalize(a):
    return a / jnp.sqrt(jnp.maximum(_dot(a, a), 1e-30))[..., None]


def flatten_scene(desc: dict) -> dict:
    """All meshes as one triangle soup (numpy, float64 until cast): vertex
    positions, per-corner shading normals (the geometric normal where a mesh
    has none), reflectance, emitted radiance, and the emitters' table."""
    v0, v1, v2, n0, n1, n2, kd, le = ([] for _ in range(8))
    for m in desc["meshes"]:
        P = np.asarray(m["P"], np.float32).astype(np.float64)
        F = np.asarray(m["indices"], np.int64)
        a, b, c = P[F[:, 0]], P[F[:, 1]], P[F[:, 2]]
        ng = np.cross(b - a, c - a)
        ng /= np.maximum(np.linalg.norm(ng, axis=-1, keepdims=True), 1e-30)
        if m.get("N") is not None:
            N = np.asarray(m["N"], np.float32).astype(np.float64)
            na, nb, nc = N[F[:, 0]], N[F[:, 1]], N[F[:, 2]]
        else:
            na = nb = nc = ng
        v0.append(a), v1.append(b), v2.append(c)
        n0.append(na), n1.append(nb), n2.append(nc)
        kd.append(np.broadcast_to(np.asarray(m["Kd"], np.float64), a.shape))
        L = m.get("L")
        le.append(np.broadcast_to(np.zeros(3) if L is None else np.asarray(L, np.float64), a.shape))
    cat = lambda xs: np.concatenate(xs, axis=0)  # noqa: E731
    v0, v1, v2, kd, le = cat(v0), cat(v1), cat(v2), cat(kd), cat(le)
    n0, n1, n2 = cat(n0), cat(n1), cat(n2)
    T = len(v0)
    pad = (-T) % TRI_BLOCK if T > TRI_BLOCK else 0

    def padded(x, fill=0.0):
        return np.concatenate([x, np.full((pad,) + x.shape[1:], fill)], axis=0) if pad else x

    area = 0.5 * np.linalg.norm(np.cross(v1 - v0, v2 - v0), axis=-1)
    em = np.flatnonzero(le.max(axis=-1) > 0)
    # one light per emitting mesh in the program; the reference samples all
    # emitting triangles as one light, by area, which has the same expectation
    em_cdf = np.cumsum(area[em]) / max(area[em].sum(), 1e-30) if len(em) else np.zeros(0)
    return {
        # degenerate padding triangles (all three corners far away, zero area)
        "v0": padded(v0, 1e30), "e1": padded(v1 - v0), "e2": padded(v2 - v0),
        "n0": padded(n0), "n1": padded(n1), "n2": padded(n2),
        "kd": padded(kd), "le": padded(le),
        "em_idx": em.astype(np.int32), "em_cdf": em_cdf, "em_area": float(area[em].sum()),
        "point_from": np.asarray([p["from"] for p in desc.get("point_lights", [])], np.float64).reshape(-1, 3),
        "point_I": np.asarray([p["I"] for p in desc.get("point_lights", [])], np.float64).reshape(-1, 3),
        "n_tris": T,
    }


def _camera(desc: dict):
    cam, film = desc["camera"], desc["film"]
    eye, look, up = (np.asarray(cam[k], np.float64) for k in ("eye", "look", "up"))
    fwd = (look - eye) / np.linalg.norm(look - eye)
    right = np.cross(up / np.linalg.norm(up), fwd)
    right /= np.linalg.norm(right)
    new_up = np.cross(fwd, right)
    xres, yres = int(film["xres"]), int(film["yres"])
    aspect = xres / yres
    sx, sy = (aspect, 1.0) if aspect > 1 else (1.0, 1.0 / aspect)
    tan_half = math.tan(math.radians(float(cam["fov"])) / 2)
    return eye, right, new_up, fwd, xres, yres, sx * tan_half, sy * tan_half


def make_renderer(desc: dict, ray_block: int, dtype=jnp.float32, intersect_dtype=None):
    """-> render(pix_xy (N,2) int32, spp, seed) -> (N,3) float64 mean radiance.
    N * spp must be a multiple of ray_block."""
    fs = flatten_scene(desc)
    np_dtype = np.dtype(dtype)  # ml_dtypes gives numpy a bfloat16
    idtype = dtype if intersect_dtype is None else intersect_dtype
    T = fs["v0"].shape[0]
    nb = max(T // TRI_BLOCK, 1)
    tb = T // nb
    eye, right, new_up, fwd, xres, yres, tx, ty = _camera(desc)
    # Everything the program below reads is prepared on the host and handed
    # to it as ARGUMENTS: no eager device op (each would be a small compile
    # in every run) and no scene baked into the program as a constant.
    A = {k: fs[k].astype(np_dtype) for k in
         ("v0", "e1", "e2", "n0", "n1", "n2", "kd", "le", "point_from", "point_I")}
    for k in ("v0", "e1", "e2"):
        # (nb, 3, tb): triangles on the minor axis, one row per component
        A[k + "b"] = np.ascontiguousarray(
            np.transpose(fs[k].astype(np.dtype(idtype)).reshape(nb, tb, 3), (0, 2, 1)))
    A.update(
        em_idx=fs["em_idx"], em_cdf=fs["em_cdf"].astype(np.float32),
        eye=eye.astype(np_dtype), right=right.astype(np_dtype),
        new_up=new_up.astype(np_dtype), fwd=fwd.astype(np_dtype),
    )
    em_area = fs["em_area"]
    has_area = len(fs["em_idx"]) > 0
    n_point = len(fs["point_from"])
    maxdepth = int(desc["maxdepth"])
    inf = float("inf")

    def intersect(A, o, d, t_max):
        """Closest hit of each ray over all triangles -> (t, tri) with
        t = inf on a miss. o, d: (R,3); t_max: (R,)."""
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

    def paths(key, pix, A):
        """One block of R paths -> (R,3) radiance. A: the scene's arrays."""
        v0, e1, e2, n0, n1, n2, kd, le = (A[k] for k in ("v0", "e1", "e2", "n0", "n1", "n2", "kd", "le"))
        R = pix.shape[0]
        u = jax.random.uniform(key, (R, 2 + 5 * maxdepth), jnp.float32).astype(dtype)
        fx = pix[:, 0].astype(dtype) + u[:, 0]
        fy = pix[:, 1].astype(dtype) + u[:, 1]
        cx = ((2.0 * fx / xres - 1.0) * tx).astype(dtype)
        cy = ((1.0 - 2.0 * fy / yres) * ty).astype(dtype)
        d = _normalize(cx[:, None] * A["right"] + cy[:, None] * A["new_up"] + A["fwd"])
        o = jnp.broadcast_to(A["eye"], d.shape)
        L = jnp.zeros((R, 3), dtype)
        beta = jnp.ones((R, 3), dtype)
        alive = jnp.ones((R,), bool)
        for depth in range(maxdepth):
            uu = u[:, 2 + 5 * depth : 7 + 5 * depth]
            t, tri = intersect(A, o, d, jnp.where(alive, inf, -1.0).astype(dtype))
            hit = alive & (tri >= 0)
            tri = jnp.maximum(tri, 0)
            a, b, c = v0[tri], e1[tri], e2[tri]
            p = o + jnp.where(hit, t, 0.0)[:, None] * d
            ng = _normalize(_cross(b, c))
            # barycentrics of p for the shading normal
            pv = _cross(d, c)
            inv = 1.0 / jnp.where(hit, _dot(b, pv), 1.0)
            s = o - a
            bu = _dot(s, pv) * inv
            bv = _dot(d, _cross(s, b)) * inv
            ns = _normalize((1.0 - bu - bv)[:, None] * n0[tri] + bu[:, None] * n1[tri] + bv[:, None] * n2[tri])
            if depth == 0:
                # a camera ray sees a light's own radiance on its emitting side
                L = L + jnp.where((hit & (_dot(ng, d) < 0))[:, None], le[tri], 0.0)
            ng = jnp.where((_dot(ng, ns) < 0)[:, None], -ng, ng)
            wo = -d
            k = kd[tri]
            # ---- direct light: every light sampled once (one point on the
            # emitters by area, each point light), a shadow ray each
            lights = []  # (wi, distance, incident radiance) per light
            if has_area:
                em_idx = A["em_idx"]
                j = jnp.searchsorted(A["em_cdf"], uu[:, 0].astype(jnp.float32), side="right")
                lt = em_idx[jnp.minimum(j, em_idx.shape[0] - 1)]
                su = jnp.sqrt(uu[:, 1])
                b0, b1 = 1.0 - su, uu[:, 2] * su
                pl = v0[lt] + b1[:, None] * e1[lt] + (1.0 - b0 - b1)[:, None] * e2[lt]
                to_l = pl - p
                d2 = jnp.maximum(_dot(to_l, to_l), 1e-20)
                w = to_l / jnp.sqrt(d2)[:, None]
                nl = _normalize(_cross(e1[lt], e2[lt]))
                cos_l = jnp.maximum(-_dot(nl, w), 0.0)
                lights.append((w, jnp.sqrt(d2), le[lt] * (cos_l * em_area / d2).astype(dtype)[:, None]))
            for q in range(n_point):
                to_l = A["point_from"][q] - p
                d2 = jnp.maximum(_dot(to_l, to_l), 1e-20)
                lights.append((to_l / jnp.sqrt(d2)[:, None], jnp.sqrt(d2), A["point_I"][q] / d2[:, None]))
            for wi_l, dist, li in lights:
                reflect = _dot(wi_l, ng) * _dot(wo, ng) > 0
                lit = hit & reflect & (jnp.max(li, axis=-1) > 0)
                ts, _ = intersect(
                    A, offset(p, ng, wi_l), wi_l,
                    jnp.where(lit, dist * (1.0 - 1e-3), -1.0).astype(dtype),
                )
                vis = lit & ~(ts < inf)
                fcos = (jnp.abs(_dot(wi_l, ns)) * (1.0 / math.pi)).astype(dtype)
                L = L + jnp.where(vis[:, None], beta * k * li * fcos[:, None], 0.0)
            # ---- continue: cosine-weighted direction about the shading
            # normal, on wo's side of it (f cos / pdf = Kd)
            if depth + 1 < maxdepth:
                r = jnp.sqrt(uu[:, 3])
                phi = ((2.0 * math.pi) * uu[:, 4]).astype(dtype)
                lx, ly = r * jnp.cos(phi), r * jnp.sin(phi)
                lz = jnp.sqrt(jnp.maximum(1.0 - uu[:, 3], 0.0))
                nz = jnp.where((_dot(wo, ns) < 0)[:, None], -ns, ns)
                helper = jnp.where((jnp.abs(nz[:, 0]) > 0.9)[:, None],
                                   jnp.asarray([0.0, 1.0, 0.0], dtype), jnp.asarray([1.0, 0.0, 0.0], dtype))
                sx_ = _normalize(_cross(helper, nz))
                sy_ = _cross(nz, sx_)
                wi = _normalize(lx[:, None] * sx_ + ly[:, None] * sy_ + lz[:, None] * nz)
                alive = hit & (_dot(wi, ng) * _dot(wo, ng) > 0)
                beta = beta * k
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
