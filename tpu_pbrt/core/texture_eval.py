"""Device texture evaluation (VERDICT r3 #6).

Capability match for pbrt-v3 src/core/texture.{h,cpp} (Texture::Evaluate,
the 2D/3D mappings, Noise/FBm/Turbulence), src/core/mipmap.h (MIPMap
pyramid + trilinear lookup), and src/textures/* evaluation semantics
(imagemap, checkerboard, dots, scale, mix, bilerp, uv, fbm, wrinkled,
windy, marble).

TPU-first design: textures are COMPILED, not interpreted. The scene
compiler hands the (small, static) set of non-constant texture nodes to
`build_texture_table`, which
- packs every imagemap's full mip pyramid into ONE flat (T, 3) f32 atlas
  buffer (level offsets/extents are Python constants baked into each
  texture's generated closure — no metadata table, no indirection), and
- generates one jitted evaluator closure per texture node tree by
  recursive composition; per-lane texture selection is a masked sum over
  the (few) per-scene textures rather than lax.switch, because the ids
  are per-lane, not scalar.

Lookups use bilinear filtering at an explicit mip level (default 0 —
pbrt's no-ray-differentials path collapses to the finest level the same
way). When the caller supplies the (..., 4) [dudx, dvdx, dudy, dvdy]
uv-footprint (camera hits through ray differentials), imagemaps run the
EWA-class anisotropic filter: mip level from the minor ellipse axis,
EWA_TAPS Gaussian-weighted trilinear taps along the major axis,
eccentricity clamped to MAX_ANISO (mipmap.h MIPMap::EWA semantics,
realized as fixed-tap footprint assembly — a TPU-static formulation of
the same ellipse integral; the data-dependent ellipse-bbox loop of the
reference would defeat XLA). A legacy scalar lod takes one trilinear
tap. Gamma decode (sRGB->linear) happens once at load, as in
imagemap.cpp's ConvertIn(gamma).

The procedural noise is a hash-based lattice gradient noise with pbrt's
quintic smoothstep weights and FBm/Turbulence octave accumulation
(omega gain, 1.99 lacunarity). pbrt seeds gradients from a fixed
permutation table; ours come from an integer hash — statistically
equivalent, not bit-identical (documented deviation).
"""

from __future__ import annotations

from typing import Any, Callable, List, Tuple

import jax.numpy as jnp
import numpy as np

from tpu_pbrt.core.vecmath import linear3

#: EWA eccentricity clamp (pbrt ImageTexture maxanisotropy default)
MAX_ANISO = 8.0
#: fixed Gaussian tap count along the major axis (static cost per lane;
#: 4 matches common hardware aniso quality at 8:1 eccentricity)
EWA_TAPS = 4

# -------------------------------------------------------------------------
# noise (texture.cpp Noise/FBm/Turbulence)
# -------------------------------------------------------------------------


def _hash3(xi, yi, zi):
    h = (
        xi.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        ^ yi.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        ^ zi.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
    )
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x2C1B3C6D)
    h = h ^ (h >> 12)
    return h


def _grad(xi, yi, zi, dx, dy, dz):
    """Gradient dot product from one of 16 lattice directions (the
    classic Perlin gradient set, selected by hash instead of pbrt's
    permutation table)."""
    h = _hash3(xi, yi, zi) & 15
    u = jnp.where(h < 8, dx, dy)
    v = jnp.where(h < 4, dy, jnp.where((h == 12) | (h == 14), dx, dz))
    return jnp.where(h & 1 == 0, u, -u) + jnp.where(h & 2 == 0, v, -v)


def noise3(p):
    """Perlin-style gradient noise in [-1, 1], p: (..., 3)."""
    pi = jnp.floor(p)
    d = p - pi
    xi = pi[..., 0].astype(jnp.int32)
    yi = pi[..., 1].astype(jnp.int32)
    zi = pi[..., 2].astype(jnp.int32)
    dx, dy, dz = d[..., 0], d[..., 1], d[..., 2]
    # quintic smoothstep (NoiseWeight in texture.cpp)
    w = d * d * d * (d * (d * 6.0 - 15.0) + 10.0)
    wx, wy, wz = w[..., 0], w[..., 1], w[..., 2]

    def g(ox, oy, oz):
        return _grad(xi + ox, yi + oy, zi + oz, dx - ox, dy - oy, dz - oz)

    def lerp(t, a, b):
        return a + t * (b - a)

    x00 = lerp(wx, g(0, 0, 0), g(1, 0, 0))
    x10 = lerp(wx, g(0, 1, 0), g(1, 1, 0))
    x01 = lerp(wx, g(0, 0, 1), g(1, 0, 1))
    x11 = lerp(wx, g(0, 1, 1), g(1, 1, 1))
    y0 = lerp(wy, x00, x10)
    y1 = lerp(wy, x01, x11)
    return lerp(wz, y0, y1)


def fbm(p, omega: float, octaves: int):
    """texture.cpp FBm (no ray-differential octave clamp: explicit count)."""
    out = 0.0
    lam, o = 1.0, 1.0
    for _ in range(max(int(octaves), 1)):
        out = out + o * noise3(p * lam)
        lam *= 1.99
        o *= omega
    return out


def turbulence(p, omega: float, octaves: int):
    out = 0.0
    lam, o = 1.0, 1.0
    for _ in range(max(int(octaves), 1)):
        out = out + o * jnp.abs(noise3(p * lam))
        lam *= 1.99
        o *= omega
    return out


# -------------------------------------------------------------------------
# mappings (texture.cpp TextureMapping2D/3D)
# -------------------------------------------------------------------------


def _map2d(m: dict, uv, p):
    kind = m.get("type", "uv")
    if kind == "uv":
        u = m["su"] * uv[..., 0] + m["du"]
        v = m["sv"] * uv[..., 1] + m["dv"]
        return u, v
    if kind == "planar":
        v1 = jnp.asarray(m["v1"], jnp.float32)
        v2 = jnp.asarray(m["v2"], jnp.float32)
        return (
            jnp.sum(p * v1, -1) + m["du"],
            jnp.sum(p * v2, -1) + m["dv"],
        )
    w2t = np.asarray(m["world_to_texture"].m, np.float32)
    pt = linear3(w2t[:3, :3], p) + w2t[:3, 3]
    if kind == "spherical":
        r = jnp.linalg.norm(pt, axis=-1)
        theta = jnp.arccos(jnp.clip(pt[..., 2] / jnp.maximum(r, 1e-20), -1, 1))
        phi = jnp.arctan2(pt[..., 1], pt[..., 0])
        phi = jnp.where(phi < 0, phi + 2 * np.pi, phi)
        return theta / np.pi, phi / (2 * np.pi)
    # cylindrical
    phi = jnp.arctan2(pt[..., 1], pt[..., 0])
    phi = jnp.where(phi < 0, phi + 2 * np.pi, phi)
    return phi / (2 * np.pi), pt[..., 2]


def _map3d(m: dict, p):
    w2t = np.asarray(m["world_to_texture"].m, np.float32)
    return linear3(w2t[:3, :3], p) + w2t[:3, 3]


# -------------------------------------------------------------------------
# imagemap atlas
# -------------------------------------------------------------------------


def _srgb_to_linear(x):
    return np.where(x <= 0.04045, x / 12.92, ((x + 0.055) / 1.055) ** 2.4)


def _build_pyramid(img: np.ndarray) -> List[np.ndarray]:
    """Box-filtered mip chain (mipmap.h resampleWeights simplified to the
    power-of-two box reduction; non-pow2 levels use edge-clamped halving)."""
    levels = [img.astype(np.float32)]
    cur = levels[0]
    while max(cur.shape[0], cur.shape[1]) > 1:
        h, w = cur.shape[:2]
        h2, w2 = max(h // 2, 1), max(w // 2, 1)
        pad = cur[: h2 * 2, : w2 * 2]
        if pad.shape[0] < 2 * h2 or pad.shape[1] < 2 * w2:
            pad = np.pad(
                cur,
                ((0, 2 * h2 - h), (0, 2 * w2 - w), (0, 0)),
                mode="edge",
            )[: 2 * h2, : 2 * w2]
        nxt = 0.25 * (
            pad[0::2, 0::2] + pad[1::2, 0::2] + pad[0::2, 1::2] + pad[1::2, 1::2]
        )
        levels.append(nxt.astype(np.float32))
        cur = nxt
    return levels


def _bilinear(atlas, off: int, w: int, h: int, u, v, wrap: str):
    """One bilinear tap from a level stored row-major at atlas[off:off+w*h]."""
    x = u * w - 0.5
    y = v * h - 0.5
    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    fx = x - x0
    fy = y - y0

    def wrapc(i, n):
        i = i.astype(jnp.int32)
        if wrap == "repeat":
            return jnp.mod(i, n)
        return jnp.clip(i, 0, n - 1)

    inside = jnp.ones(u.shape, bool)
    if wrap == "black":
        inside = (u >= 0.0) & (u < 1.0) & (v >= 0.0) & (v < 1.0)

    def tap(ix, iy):
        idx = off + wrapc(iy, h) * w + wrapc(ix, w)
        return atlas[idx]

    c = (
        tap(x0, y0) * ((1 - fx) * (1 - fy))[..., None]
        + tap(x0 + 1, y0) * (fx * (1 - fy))[..., None]
        + tap(x0, y0 + 1) * ((1 - fx) * fy)[..., None]
        + tap(x0 + 1, y0 + 1) * (fx * fy)[..., None]
    )
    return jnp.where(inside[..., None], c, 0.0)


# -------------------------------------------------------------------------
# node compilation
# -------------------------------------------------------------------------


class _AtlasBuilder:
    def __init__(self):
        self.chunks: List[np.ndarray] = []
        self.size = 0
        self._cache = {}

    def add_image(self, path: str, gamma: bool, scale: float):
        """Returns [(offset, w, h)] per mip level."""
        key = (path, bool(gamma), float(scale))
        if key in self._cache:
            return self._cache[key]
        from tpu_pbrt.utils.imageio import read_image

        img = np.asarray(read_image(path), np.float32)
        if img.ndim == 2:
            img = img[..., None]
        if img.shape[-1] == 1:
            img = np.repeat(img, 3, -1)
        img = img[..., :3]
        if gamma:
            img = _srgb_to_linear(img)
        img = img * scale
        levels = []
        for lv in _build_pyramid(img):
            h, w = lv.shape[:2]
            levels.append((self.size, w, h))
            self.chunks.append(lv.reshape(-1, 3))
            self.size += w * h
        self._cache[key] = levels
        return levels

    def finish(self) -> np.ndarray:
        if not self.chunks:
            return np.zeros((1, 3), np.float32)
        return np.concatenate(self.chunks, 0)


def _compile_node(node, atlas: _AtlasBuilder) -> Callable:
    """node -> fn(atlas_buf, uv, p, lod) -> (..., 3). Constants and float
    scalars broadcast; recursion composes sub-textures (scale/mix/checker
    arms are themselves texture nodes)."""
    if node is None:
        return lambda a, uv, p, lod: jnp.zeros(uv.shape[:-1] + (3,), jnp.float32)
    if isinstance(node, (int, float)):
        c = float(node)
        return lambda a, uv, p, lod: jnp.full(uv.shape[:-1] + (3,), c, jnp.float32)
    if isinstance(node, np.ndarray) or (
        isinstance(node, (list, tuple)) and node and isinstance(node[0], (int, float))
    ):
        c = np.asarray(node, np.float32).reshape(-1)
        c3 = np.full(3, c[0]) if c.size == 1 else c[:3]
        return lambda a, uv, p, lod: jnp.broadcast_to(
            jnp.asarray(c3), uv.shape[:-1] + (3,)
        )
    kind = node[0]
    if kind in ("const", "constf"):
        return _compile_node(node[1], atlas)
    if kind == "scale":
        f1 = _compile_node(node[1], atlas)
        f2 = _compile_node(node[2], atlas)
        return lambda a, uv, p, lod: f1(a, uv, p, lod) * f2(a, uv, p, lod)
    if kind == "mix":
        f1 = _compile_node(node[1], atlas)
        f2 = _compile_node(node[2], atlas)
        fa = _compile_node(node[3], atlas)
        return lambda a, uv, p, lod: (
            lambda t: (1.0 - t) * f1(a, uv, p, lod) + t * f2(a, uv, p, lod)
        )(fa(a, uv, p, lod))
    if kind == "bilerp":
        d = node[1]
        f00 = _compile_node(d["v00"], atlas)
        f01 = _compile_node(d["v01"], atlas)
        f10 = _compile_node(d["v10"], atlas)
        f11 = _compile_node(d["v11"], atlas)
        m = d["mapping"]

        def ev_bilerp(a, uv, p, lod):
            u, v = _map2d(m, uv, p)
            return (
                (1 - u)[..., None] * (1 - v)[..., None] * f00(a, uv, p, lod)
                + (1 - u)[..., None] * v[..., None] * f01(a, uv, p, lod)
                + u[..., None] * (1 - v)[..., None] * f10(a, uv, p, lod)
                + u[..., None] * v[..., None] * f11(a, uv, p, lod)
            )

        return ev_bilerp
    if kind == "imagemap":
        d = node[1]
        levels = atlas.add_image(d["filename"], d["gamma"], d["scale"])
        m = d["mapping"]
        wrap = d.get("wrap", "repeat")
        n_levels = len(levels)

        def trilerp(a, u, v, lodc):
            """One trilinear tap: bilinear at floor/ceil level, lerped."""
            l0 = jnp.floor(lodc).astype(jnp.int32)
            fl = lodc - l0.astype(jnp.float32)
            out0 = jnp.zeros(u.shape + (3,), jnp.float32)
            out1 = jnp.zeros(u.shape + (3,), jnp.float32)
            for li, (off, w, h) in enumerate(levels):
                tapv = _bilinear(a, off, w, h, u, v, wrap)
                out0 = jnp.where((l0 == li)[..., None], tapv, out0)
                out1 = jnp.where(
                    (jnp.minimum(l0 + 1, n_levels - 1) == li)[..., None],
                    tapv, out1,
                )
            return out0 * (1.0 - fl)[..., None] + out1 * fl[..., None]

        def ev_image(a, uv, p, lod):
            u, v = _map2d(m, uv, p)
            if lod is None:
                off, w, h = levels[0]
                return _bilinear(a, off, w, h, u, v, wrap)
            # `lod` is the (..., 4) [dudx, dvdx, dudy, dvdy] SURFACE-uv
            # footprint; the uv mapping's su/sv scale it into texture
            # space exactly as UVMapping2D::Map scales dstdx/dstdy
            # before MIPMap::Lookup (other mappings approximate with
            # scale 1). A legacy scalar `lod` (isotropic width) still
            # takes the single-tap trilinear path.
            if lod.ndim == u.ndim + 1:
                # ---- EWA-class anisotropic filtering (mipmap.h EWA,
                # realized as footprint assembly): pick the mip level
                # from the MINOR ellipse axis and place EWA_TAPS
                # Gaussian-weighted trilinear taps along the MAJOR
                # axis. Fixed tap count keeps the cost static (TPU:
                # no data-dependent ellipse-bbox loop); eccentricity
                # clamped to MAX_ANISO exactly as pbrt widens the
                # minor axis.
                if m.get("type", "uv") == "uv":
                    su = abs(float(m.get("su", 1.0)))
                    sv = abs(float(m.get("sv", 1.0)))
                else:
                    su = sv = 1.0
                dux, dvx = lod[..., 0] * su, lod[..., 1] * sv
                duy, dvy = lod[..., 2] * su, lod[..., 3] * sv
                l2x = dux * dux + dvx * dvx
                l2y = duy * duy + dvy * dvy
                x_major = l2x >= l2y
                major = jnp.sqrt(jnp.maximum(jnp.maximum(l2x, l2y), 1e-16))
                minor = jnp.sqrt(jnp.maximum(jnp.minimum(l2x, l2y), 0.0))
                minor = jnp.maximum(minor, major / MAX_ANISO)
                mu = jnp.where(x_major, dux, duy)
                mv = jnp.where(x_major, dvx, dvy)
                lodc = jnp.clip(
                    (n_levels - 1)
                    + jnp.log2(jnp.maximum(minor, 1e-8)),
                    0.0, n_levels - 1.0,
                )
                acc = jnp.zeros(u.shape + (3,), jnp.float32)
                wsum = 0.0
                for t in range(EWA_TAPS):
                    f = (t + 0.5) / EWA_TAPS - 0.5  # (-0.5, 0.5)
                    # pbrt's EWA Gaussian falloff (alpha = 2) over the
                    # normalized ellipse coordinate r = 2f
                    wgt = float(np.exp(-2.0 * (2.0 * f) ** 2))
                    acc = acc + wgt * trilerp(
                        a, u + f * mu, v + f * mv, lodc
                    )
                    wsum += wgt
                return acc / wsum
            map_scale = max(
                abs(float(m.get("su", 1.0))), abs(float(m.get("sv", 1.0)))
            ) if m.get("type", "uv") == "uv" else 1.0
            lvl = (n_levels - 1) + jnp.log2(
                jnp.maximum(lod * map_scale, 1e-8)
            )
            return trilerp(a, u, v, jnp.clip(lvl, 0.0, n_levels - 1.0))

        return ev_image
    if kind == "uv":
        m = node[1]["mapping"]

        def ev_uv(a, uv, p, lod):
            u, v = _map2d(m, uv, p)
            return jnp.stack([u - jnp.floor(u), v - jnp.floor(v), jnp.zeros_like(u)], -1)

        return ev_uv
    if kind == "checkerboard":
        d = node[1]
        f1 = _compile_node(d["tex1"], atlas)
        f2 = _compile_node(d["tex2"], atlas)
        m = d["mapping"]
        if d["dim"] == 2:

            def ev_check(a, uv, p, lod):
                u, v = _map2d(m, uv, p)
                sel = (jnp.floor(u) + jnp.floor(v)).astype(jnp.int32) % 2 == 0
                return jnp.where(sel[..., None], f1(a, uv, p, lod), f2(a, uv, p, lod))

            return ev_check

        def ev_check3(a, uv, p, lod):
            pt = _map3d(m, p)
            s = jnp.sum(jnp.floor(pt).astype(jnp.int32), -1)
            return jnp.where((s % 2 == 0)[..., None], f1(a, uv, p, lod), f2(a, uv, p, lod))

        return ev_check3
    if kind == "dots":
        d = node[1]
        fi = _compile_node(d["inside"], atlas)
        fo = _compile_node(d["outside"], atlas)
        m = d["mapping"]

        def ev_dots(a, uv, p, lod):
            u, v = _map2d(m, uv, p)
            sc, tc = jnp.floor(u + 0.5), jnp.floor(v + 0.5)
            cell = jnp.stack([sc, tc, jnp.zeros_like(sc)], -1)
            has_dot = noise3(cell + 0.5) > 0.0
            rad = 0.35
            maxshift = 0.5 - rad
            cx = sc + maxshift * noise3(cell * 1.5 + 10.0)
            cy = tc + maxshift * noise3(cell * 2.5 + 20.0)
            d2 = (u - cx) ** 2 + (v - cy) ** 2
            sel = has_dot & (d2 < rad * rad)
            return jnp.where(sel[..., None], fi(a, uv, p, lod), fo(a, uv, p, lod))

        return ev_dots
    if kind in ("fbm", "wrinkled", "windy", "marble"):
        d = node[1]
        m = d["mapping"]
        octaves = int(d.get("octaves", 8))
        omega = float(d.get("roughness", 0.5))
        if kind == "fbm":

            def ev_noise(a, uv, p, lod):
                return fbm(_map3d(m, p), omega, octaves)[..., None] * jnp.ones(3)

            return ev_noise
        if kind == "wrinkled":

            def ev_wri(a, uv, p, lod):
                return turbulence(_map3d(m, p), omega, octaves)[..., None] * jnp.ones(3)

            return ev_wri
        if kind == "windy":

            def ev_windy(a, uv, p, lod):
                pt = _map3d(m, p)
                strength = jnp.abs(fbm(0.1 * pt, 0.5, 3))
                height = fbm(pt, 0.5, 6)
                return (strength * jnp.abs(height))[..., None] * jnp.ones(3)

            return ev_windy
        scale = float(d.get("scale", 1.0))
        variation = float(d.get("variation", 0.2))
        # marble.cpp: sin curve displaced by turbulence, spline through
        # the marble color ramp (colors approximated by the ramp below)
        _MARBLE = np.asarray(
            [
                [0.58, 0.58, 0.6],
                [0.58, 0.58, 0.6],
                [0.58, 0.58, 0.6],
                [0.5, 0.5, 0.5],
                [0.6, 0.59, 0.58],
                [0.58, 0.58, 0.6],
                [0.58, 0.58, 0.6],
                [0.2, 0.2, 0.33],
                [0.58, 0.58, 0.6],
            ],
            np.float32,
        )

        def ev_marble(a, uv, p, lod):
            pt = _map3d(m, p) * scale
            marble = pt[..., 1] + variation * fbm(pt, omega, octaves)
            t = 0.5 + 0.5 * jnp.sin(marble)
            nseg = _MARBLE.shape[0] - 3
            fi = jnp.clip(t * nseg, 0.0, nseg - 1e-4)
            i0 = fi.astype(jnp.int32)
            ft = (fi - i0)[..., None]
            ramp = jnp.asarray(_MARBLE)
            c0 = ramp[i0 + 1]
            c1 = ramp[i0 + 2]
            return (1 - ft) * c0 + ft * c1

        return ev_marble
    # unknown node: mid gray (textures.py already warned at parse)
    return lambda a, uv, p, lod: jnp.full(uv.shape[:-1] + (3,), 0.5, jnp.float32)


def build_texture_table(nodes: List[Any]) -> Tuple[np.ndarray, Callable]:
    """deferred texture nodes -> (atlas ndarray, eval fn).

    eval(atlas_buf, tid (R,), uv (R,2), p (R,3), lod=None) -> (R,3);
    tid < 0 lanes return 0 (callers keep the constant-folded parameter).
    Selection is a masked sum over the per-scene texture set."""
    atlas = _AtlasBuilder()
    fns = [_compile_node(n, atlas) for n in nodes]
    buf = atlas.finish()

    def evaluate(atlas_buf, tid, uv, p, lod=None):
        out = jnp.zeros(uv.shape[:-1] + (3,), jnp.float32)
        for i, fn in enumerate(fns):
            val = fn(atlas_buf, uv, p, lod)
            if val.ndim == out.ndim - 1:
                val = val[..., None] * jnp.ones((3,), jnp.float32)
            out = jnp.where((tid == i)[..., None], val, out)
        return out

    return buf, evaluate
