"""Scene compiler: parsed scene records -> flat SoA device arrays.

This is the TPU-first replacement for pbrt-v3's object graph. Where pbrt
builds a tree of virtual-dispatch objects (GeometricPrimitive wrapping
Shape/Material/AreaLight; src/core/primitive.h, api.cpp MakeShapes), the
compiler lowers everything ONCE on the host into flat arrays in HBM:

- all shapes tessellated/collected into one world-space triangle soup
  (src/shapes/* capability; quadrics are tessellated, meshes are native),
- object instances (TransformedPrimitive, api.cpp pbrtObjectInstance)
  expanded by baking instance transforms,
- materials lowered to a type-enum + parameter-slot table
  (src/materials/*::ComputeScatteringFunctions capability),
- lights lowered to a type-enum SoA table; emissive shapes become one
  area-light row per triangle exactly as pbrt makes one DiffuseAreaLight
  per Triangle (api.cpp MakeShapes + diffuse.cpp),
- a BVH built over the soup and flattened to LinearBVHNode SoA
  (accelerators/bvh.cpp), with triangle arrays permuted to leaf order so
  leaf prims are contiguous in HBM,
- film/camera/sampler/integrator configs resolved via the Make* factories.

Tagged-union dispatch over the type enums replaces virtual calls inside the
wavefront kernels (SURVEY.md §7 design stance).
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from tpu_pbrt.accel.build import build_bvh, triangle_bounds
from tpu_pbrt.accel.traverse import bvh_as_device_dict
from tpu_pbrt.cameras import make_camera
from tpu_pbrt.core.film import Film, make_film
from tpu_pbrt.core.filters import make_filter
from tpu_pbrt.core.sampling import Distribution1D, Distribution2D
from tpu_pbrt.core.spectrum import luminance
from tpu_pbrt.obs.trace import TRACE
from tpu_pbrt.scene.plyreader import read_ply
from tpu_pbrt.utils.error import Error, Warning
from tpu_pbrt.utils.fileutil import resolve_filename

# material type enum (device tagged union)
MAT_NONE = 0
MAT_MATTE = 1
MAT_PLASTIC = 2
MAT_METAL = 3
MAT_GLASS = 4
MAT_MIRROR = 5
MAT_UBER = 6
MAT_SUBSTRATE = 7
MAT_TRANSLUCENT = 8
MAT_DISNEY = 9
MAT_HAIR = 10
MAT_FOURIER = 11
MAT_SUBSURFACE = 12

#: raw roughness above this makes glass a microfacet (non-delta) surface
#: (glass.cpp: rough glass builds MicrofacetReflection/Transmission)
ROUGH_GLASS_MIN = 1e-4

_MAT_ENUM = {
    "none": MAT_NONE,
    "matte": MAT_MATTE,
    "plastic": MAT_PLASTIC,
    "metal": MAT_METAL,
    "glass": MAT_GLASS,
    "mirror": MAT_MIRROR,
    "uber": MAT_UBER,
    "substrate": MAT_SUBSTRATE,
    "translucent": MAT_TRANSLUCENT,
    "disney": MAT_DISNEY,
    "hair": MAT_HAIR,
    "fourier": MAT_FOURIER,
    "subsurface": MAT_SUBSURFACE,
    "kdsubsurface": MAT_SUBSURFACE,
}

# light type enum
LIGHT_POINT = 0
LIGHT_SPOT = 1
LIGHT_DISTANT = 2
LIGHT_AREA = 3
LIGHT_INFINITE = 4
LIGHT_GONIO = 5
LIGHT_PROJECTION = 6

#: the light table's columns: width of a row, dtype on the device
LIGHT_COLUMNS = {
    "type": ((), np.int32), "p": ((3,), np.float32), "L": ((3,), np.float32),
    "dir": ((3,), np.float32), "cos0": ((), np.float32), "cos1": ((), np.float32),
    "tri": ((), np.int32), "twosided": ((), np.int32), "area": ((), np.float32),
    "w2l": ((9,), np.float32), "img": ((3,), np.int32),
}

#: what the dense spatial table (voxels x light rows x 4 bytes) may take of
#: the device: 64 MiB is 32,768 rows under the 8x8x8 grid. Past it the
#: power distribution stands in, loudly (`scene/light_distribution`)
SPATIAL_TABLE_BUDGET_BYTES = 64 << 20

LIGHT_STRATEGIES = ("uniform", "power", "spatial")


def _light_rows(n: int = 1, **given) -> Dict[str, np.ndarray]:
    """`n` rows of the light table, one array a column (float64 and int64
    until the table is assembled). A column not given is zero, but `tri`
    -1, `w2l` the identity and `img` (-1, 0, 0): no map. A value given
    once stands for all n rows."""
    rows = {}
    for name, (width, dtype) in LIGHT_COLUMNS.items():
        wide = np.int64 if np.issubdtype(dtype, np.integer) else np.float64
        fill = {"tri": -1, "w2l": np.eye(3).reshape(-1), "img": [-1, 0, 0]}.get(name, 0)
        rows[name] = np.broadcast_to(np.asarray(given.get(name, fill), wide), (n,) + width).copy()
    return rows


@dataclass
class SamplerSpec:
    name: str
    spp: int
    params: Any


@dataclass
class CompiledScene:
    """Host handle + the device pytree every kernel consumes."""

    dev: Dict[str, Any]  # device arrays (see compile_scene for schema)
    film: Film
    camera: Any  # CompiledCamera
    sampler: SamplerSpec
    integrator_name: str
    integrator_params: Any
    n_tris: int
    n_lights: int
    world_min: np.ndarray
    world_max: np.ndarray
    world_center: np.ndarray
    world_radius: float
    has_envmap: bool = False
    env_distribution: Optional[Distribution2D] = None
    light_distribution_name: str = "spatial"
    #: the strategy the light tables were built for: the one asked for, or
    #: "power" where it could not be built (`scene/light_distribution`)
    light_strategy_built: str = "power"
    light_distr: Optional[Distribution1D] = None
    media: Dict[str, Any] = field(default_factory=dict)
    camera_medium_id: int = -1
    #: scene contains MAT_NONE (interface/container) surfaces — integrators
    #: then pay for the null-passthrough visibility walk (unoccluded_tr)
    has_null_materials: bool = False
    #: a material row is a delta BSDF (smooth glass, mirror, a subsurface
    #: interface): the pool then counts the vertices at one
    #: (obs/counters.py `delta_vertices`)
    has_delta_materials: bool = False
    #: compiled texture evaluator (core/texture_eval.py) or None when every
    #: texture constant-folded; signature eval(atlas, tid, uv, p, lod=None)
    tex_eval: Any = None
    #: static set of material tex slots actually used ("kd", "ks", ...) so
    #: integrators skip evaluation entirely for untextured slots
    tex_used: frozenset = frozenset()
    #: dense per-voxel light CDFs (lights_dev.SpatialLightDistribution) or
    #: None for single-light scenes
    spatial_distr: Any = None


def _has_delta(mtab) -> bool:
    """Whether a row of the material table is a delta BSDF: a mirror, or
    glass (a subsurface interface is glass) no rougher than
    ROUGH_GLASS_MIN (bxdf.py `_lobe_flags`)."""
    mt = np.asarray(mtab["type"])
    rough = np.maximum(np.asarray(mtab["rough_u"]), np.asarray(mtab["rough_v"]))
    smooth_glass = np.isin(mt, (MAT_GLASS, MAT_SUBSURFACE)) & (rough <= ROUGH_GLASS_MIN)
    return bool(np.any(smooth_glass | (mt == MAT_MIRROR)))


# -------------------------------------------------------------------------
# Shape tessellation (host). Each returns (verts (T,3,3) f64 in OBJECT
# space, normals (T,3,3) or None, uvs (T,3,2) or None).
# -------------------------------------------------------------------------

def _tess_mesh(params, scene_dir):
    idx = params.find_int("indices")
    P = params.find_point3("P")
    if idx is None or P is None:
        Error("Vertex indices and positions \"P\" must be provided with triangle mesh.")
        return None
    idx = np.asarray(idx, np.int64).reshape(-1, 3)
    P = np.asarray(P, np.float64).reshape(-1, 3)
    N = params.find_normal("N")
    uv = params.find_point2("uv")
    if uv is None:
        uv = params.find_point2("st")
        if uv is None:
            fuv = params.find_float("uv")
            if fuv is None:
                fuv = params.find_float("st")
            uv = np.asarray(fuv, np.float64).reshape(-1, 2) if fuv is not None else None
    verts = P[idx]
    normals = np.asarray(N, np.float64).reshape(-1, 3)[idx] if N is not None else None
    uvs = np.asarray(uv, np.float64).reshape(-1, 2)[idx] if uv is not None else None
    return verts, normals, uvs


def _tess_ply(params, scene_dir):
    fn = params.find_one_string("filename", "")
    path = resolve_filename(fn, scene_dir)
    if not os.path.exists(path):
        Error(f"PLY file \"{path}\" not found.")
        return None
    with TRACE.span("scene/ply_read"):
        mesh = read_ply(path)
    idx = mesh["indices"].reshape(-1, 3)
    verts = mesh["vertices"][idx]
    normals = mesh["normals"][idx] if mesh["normals"] is not None else None
    uvs = mesh["uvs"][idx] if mesh["uvs"] is not None else None
    return verts, normals, uvs


def _grid_to_tris(px, n_u, n_v, wrap_u=False):
    """(n_v+1, n_u+1, 3) grid of points -> triangle list + uv + normals via
    finite differences left to caller. Returns vertex index triples."""
    tris = []
    for v in range(n_v):
        for u in range(n_u):
            u1 = (u + 1) % (n_u + 1) if wrap_u else u + 1
            a = v * (n_u + 1) + u
            b = v * (n_u + 1) + u1
            c = (v + 1) * (n_u + 1) + u1
            d = (v + 1) * (n_u + 1) + u
            tris.append((a, b, c))
            tris.append((a, c, d))
    return np.asarray(tris, np.int64)


def _tess_param_surface(point_fn, normal_fn, u_max, v_range, n_u, n_v):
    """Tessellate a parametric surface. point_fn(u, v) -> (3,), u in
    [0, u_max] (phi), v in v_range."""
    us = np.linspace(0.0, u_max, n_u + 1)
    vs = np.linspace(v_range[0], v_range[1], n_v + 1)
    uu, vv = np.meshgrid(us, vs)  # (n_v+1, n_u+1)
    pts = point_fn(uu, vv)  # (n_v+1, n_u+1, 3)
    nrm = normal_fn(uu, vv) if normal_fn is not None else None
    idx = _grid_to_tris(pts, n_u, n_v)
    flat_p = pts.reshape(-1, 3)
    verts = flat_p[idx]
    normals = nrm.reshape(-1, 3)[idx] if nrm is not None else None
    v_den = v_range[1] - v_range[0]
    if abs(v_den) < 1e-9:
        v_den = 1e-9
    uvn = np.stack([uu / max(u_max, 1e-9), (vv - v_range[0]) / v_den], axis=-1)
    uvs = uvn.reshape(-1, 2)[idx]
    return verts, normals, uvs


def _tess_sphere(params, scene_dir):
    r = params.find_one_float("radius", 1.0)
    zmin = params.find_one_float("zmin", -r)
    zmax = params.find_one_float("zmax", r)
    phimax = math.radians(params.find_one_float("phimax", 360.0))
    theta_min = math.acos(np.clip(zmin / r, -1, 1))
    theta_max = math.acos(np.clip(zmax / r, -1, 1))
    n_u, n_v = 64, 32

    def pt(u, v):
        # v: theta from theta_min(at zmin)→theta_max; pbrt params z from zmin..zmax
        theta = v
        return np.stack(
            [r * np.sin(theta) * np.cos(u), r * np.sin(theta) * np.sin(u), r * np.cos(theta)],
            axis=-1,
        )

    def nrm(u, v):
        p = pt(u, v)
        return p / r

    return _tess_param_surface(pt, nrm, phimax, (theta_min, theta_max), n_u, n_v)


def _tess_disk(params, scene_dir):
    h = params.find_one_float("height", 0.0)
    r = params.find_one_float("radius", 1.0)
    ri = params.find_one_float("innerradius", 0.0)
    phimax = math.radians(params.find_one_float("phimax", 360.0))
    n_u, n_v = 64, 1

    def pt(u, v):
        rad = ri + (r - ri) * v
        return np.stack([rad * np.cos(u), rad * np.sin(u), np.full_like(u, h)], axis=-1)

    def nrm(u, v):
        return np.broadcast_to(np.array([0.0, 0.0, 1.0]), u.shape + (3,))

    return _tess_param_surface(pt, nrm, phimax, (0.0, 1.0), n_u, n_v)


def _tess_cylinder(params, scene_dir):
    r = params.find_one_float("radius", 1.0)
    zmin = params.find_one_float("zmin", -1.0)
    zmax = params.find_one_float("zmax", 1.0)
    phimax = math.radians(params.find_one_float("phimax", 360.0))

    def pt(u, v):
        return np.stack([r * np.cos(u), r * np.sin(u), v], axis=-1)

    def nrm(u, v):
        return np.stack([np.cos(u), np.sin(u), np.zeros_like(u)], axis=-1)

    return _tess_param_surface(pt, nrm, phimax, (zmin, zmax), 64, 8)


def _tess_cone(params, scene_dir):
    r = params.find_one_float("radius", 1.0)
    h = params.find_one_float("height", 1.0)
    phimax = math.radians(params.find_one_float("phimax", 360.0))

    def pt(u, v):
        rad = r * (1.0 - v / h)
        return np.stack([rad * np.cos(u), rad * np.sin(u), v], axis=-1)

    return _tess_param_surface(pt, None, phimax, (0.0, h * (1 - 1e-6)), 64, 16)


def _tess_paraboloid(params, scene_dir):
    r = params.find_one_float("radius", 1.0)
    zmin = params.find_one_float("zmin", 0.0)
    zmax = params.find_one_float("zmax", 1.0)
    phimax = math.radians(params.find_one_float("phimax", 360.0))

    def pt(u, v):
        rad = r * np.sqrt(np.maximum(v, 0.0) / zmax)
        return np.stack([rad * np.cos(u), rad * np.sin(u), v], axis=-1)

    return _tess_param_surface(pt, None, phimax, (zmin, zmax), 64, 16)


def _tess_hyperboloid(params, scene_dir):
    p1 = np.asarray(params.find_one_point3("p1", [0.0, 0.0, 0.0]), np.float64)
    p2 = np.asarray(params.find_one_point3("p2", [1.0, 1.0, 1.0]), np.float64)
    phimax = math.radians(params.find_one_float("phimax", 360.0))

    def pt(u, v):
        p = p1[None, None] * (1 - v[..., None]) + p2[None, None] * v[..., None]
        xr = np.cos(u) * p[..., 0] - np.sin(u) * p[..., 1]
        yr = np.sin(u) * p[..., 0] + np.cos(u) * p[..., 1]
        return np.stack([xr, yr, p[..., 2]], axis=-1)

    return _tess_param_surface(pt, None, phimax, (0.0, 1.0), 64, 16)


def _tess_heightfield(params, scene_dir):
    nu = params.find_one_int("nu", -1)
    nv = params.find_one_int("nv", -1)
    z = params.find_float("Pz")
    if nu <= 0 or nv <= 0 or z is None:
        Error("heightfield2 requires nu, nv, Pz")
        return None
    z = np.asarray(z, np.float64).reshape(nv, nu)
    xs = np.linspace(0, 1, nu)
    ys = np.linspace(0, 1, nv)
    xx, yy = np.meshgrid(xs, ys)
    pts = np.stack([xx, yy, z], axis=-1)
    idx = _grid_to_tris(pts, nu - 1, nv - 1)
    flat = pts.reshape(-1, 3)
    uv = np.stack([xx, yy], axis=-1).reshape(-1, 2)
    return flat[idx], None, uv[idx]


def _tess_loopsubdiv(params, scene_dir):
    from tpu_pbrt.shapes.loopsubdiv import loop_subdivide

    levels = params.find_one_int("levels", params.find_one_int("nlevels", 3))
    idx = params.find_int("indices")
    P = params.find_point3("P")
    if idx is None or P is None:
        Error("loopsubdiv requires indices and P")
        return None
    verts, normals = loop_subdivide(
        np.asarray(P, np.float64).reshape(-1, 3), np.asarray(idx, np.int64).reshape(-1, 3), levels
    )
    return verts, normals, None


def _tess_curve(params, scene_dir):
    """shapes/curve.cpp capability: cubic Bezier hair/fur segments.

    pbrt intersects the curve analytically by recursive subdivision; the
    TPU-first mapping TESSELLATES each segment into a camera-independent
    flat ribbon strip (the same geometric model pbrt's "flat" curves use —
    ribbons that ignore orientation render identically under the width
    interpolation; "cylinder" curves approximate to the same ribbon). uv:
    u along the curve, v across the width."""
    cps = params.find_point3("P")
    if cps is None:
        Error("curve requires control points P")
        return None
    cps = np.asarray(cps, np.float64).reshape(-1, 3)
    if len(cps) < 4:
        Error("curve requires at least 4 control points")
        return None
    w0 = params.find_one_float("width0", params.find_one_float("width", 1.0))
    w1 = params.find_one_float("width1", params.find_one_float("width", 1.0))
    n_seg_pts = 16  # subdivisions per cubic segment
    verts_all, uvs_all = [], []
    n_curves = (len(cps) - 1) // 3  # chained cubic segments share endpoints
    for ci in range(max(n_curves, 1)):
        p0, p1, p2, p3 = cps[3 * ci : 3 * ci + 4]
        t = np.linspace(0.0, 1.0, n_seg_pts + 1)[:, None]
        b = (
            (1 - t) ** 3 * p0
            + 3 * (1 - t) ** 2 * t * p1
            + 3 * (1 - t) * t * t * p2
            + t ** 3 * p3
        )  # (n+1, 3)
        tan = (
            3 * (1 - t) ** 2 * (p1 - p0)
            + 6 * (1 - t) * t * (p2 - p1)
            + 3 * t * t * (p3 - p2)
        )
        tan /= np.maximum(np.linalg.norm(tan, axis=-1, keepdims=True), 1e-12)
        # ribbon frame: side = tangent x reference, with a per-point
        # fallback axis where the tangent turns parallel to the primary
        # reference (a single t=0-derived axis degenerates there)
        ref = np.eye(3)[np.argmin(np.abs(tan[0]))]
        side = np.cross(tan, ref)
        nrm = np.linalg.norm(side, axis=-1, keepdims=True)
        alt = np.eye(3)[(np.argmin(np.abs(tan[0])) + 1) % 3]
        side_alt = np.cross(tan, alt)
        bad = nrm < 1e-6
        side = np.where(bad, side_alt, side)
        side /= np.maximum(np.linalg.norm(side, axis=-1, keepdims=True), 1e-12)
        u_glob = (ci + t[:, 0]) / max(n_curves, 1)
        half_w = 0.5 * ((1 - u_glob) * w0 + u_glob * w1)[:, None]
        left = b - side * half_w
        right = b + side * half_w
        pts = np.stack([left, right], axis=1)  # (n+1, 2, 3)
        for k in range(n_seg_pts):
            a0, a1 = pts[k, 0], pts[k, 1]
            b0_, b1_ = pts[k + 1, 0], pts[k + 1, 1]
            verts_all += [[a0, a1, b1_], [a0, b1_, b0_]]
            ua, ub = u_glob[k], u_glob[k + 1]
            uvs_all += [
                [[ua, 0], [ua, 1], [ub, 1]],
                [[ua, 0], [ub, 1], [ub, 0]],
            ]
    return (
        np.asarray(verts_all, np.float64),
        None,
        np.asarray(uvs_all, np.float64),
    )


_TESSELATORS = {
    "trianglemesh": _tess_mesh,
    "plymesh": _tess_ply,
    "curve": _tess_curve,
    "sphere": _tess_sphere,
    "disk": _tess_disk,
    "cylinder": _tess_cylinder,
    "cone": _tess_cone,
    "paraboloid": _tess_paraboloid,
    "hyperboloid": _tess_hyperboloid,
    "heightfield2": _tess_heightfield,
    "loopsubdiv": _tess_loopsubdiv,
}


def tessellate_shape(rec) -> Optional[tuple]:
    fn = _TESSELATORS.get(rec.type)
    if fn is None:
        Warning(f'Shape "{rec.type}" unknown or not yet tessellatable; skipping.')
        return None
    return fn(rec.params, rec.scene_dir)


# -------------------------------------------------------------------------
# Texture folding: declarative texture nodes -> constant RGB/float for the
# material table; non-constant nodes get a texture id (imagemap atlas /
# procedural eval at shade time — compiled in textures_dev).
# -------------------------------------------------------------------------

def _fold_const(node, default):
    """Try to reduce a texture node to a constant; returns (value, folded)."""
    if node is None:
        return default, True
    if isinstance(node, tuple):
        tag = node[0]
        if tag in ("const", "constf"):
            return node[1], True
        if tag == "scale":
            a, fa = _fold_const(node[1], 1.0)
            b, fb = _fold_const(node[2], 1.0)
            if fa and fb:
                return np.asarray(a) * np.asarray(b), True
        if tag == "mix":
            a, fa = _fold_const(node[1], 0.0)
            b, fb = _fold_const(node[2], 1.0)
            t, ft = _fold_const(node[3], 0.5)
            if fa and fb and ft:
                return np.asarray(a) * (1 - np.asarray(t)) + np.asarray(b) * np.asarray(t), True
        return default, False
    # plain value (float or rgb array) captured directly by TextureParams
    return node, True


def _rgb(v) -> np.ndarray:
    a = np.asarray(v, np.float64).reshape(-1)
    if a.size == 1:
        return np.full(3, float(a[0]))
    return a[:3]


# -------------------------------------------------------------------------
# Material lowering
# -------------------------------------------------------------------------

_ROUGH_SLOTS = ("roughness", "uroughness", "vroughness")

#: Disney parameter slots, added to the material table only when a scene
#: actually uses the disney material (keeps every other scene's gather
#: and compile cost unchanged)
_DISNEY_SLOTS = (
    "d_metallic", "d_spectint", "d_aniso", "d_sheen", "d_sheentint",
    "d_clearcoat", "d_ccgloss", "d_strans", "d_flat", "d_dtrans",
)


def _ensure_disney_slots(tab, m):
    if "d_metallic" not in tab:
        for s in _DISNEY_SLOTS:
            tab[s] = np.zeros(m, np.float32)
        tab["d_thin"] = np.zeros(m, np.int32)


def _ensure_hair_slots(tab, m):
    if "h_beta_m" not in tab:
        tab["h_sigma_a"] = np.zeros((m, 3), np.float32)
        tab["h_beta_m"] = np.full(m, 0.3, np.float32)
        tab["h_beta_n"] = np.full(m, 0.3, np.float32)
        tab["h_alpha"] = np.full(m, 2.0, np.float32)


def _hair_sigma_a_from_reflectance(c, beta_n):
    """HairBSDF::SigmaAFromReflectance (hair.cpp)."""
    denom = (
        5.969
        - 0.215 * beta_n
        + 2.532 * beta_n**2
        - 10.73 * beta_n**3
        + 5.574 * beta_n**4
        + 0.245 * beta_n**5
    )
    return (np.log(np.maximum(np.asarray(c, np.float64), 1e-4)) / denom) ** 2


#: classic measured subsurface media (Jensen, Marschner, Levoy &
#: Hanrahan, "A Practical Model for Subsurface Light Transport",
#: SIGGRAPH 2001, table 1): name -> (sigma_prime_s, sigma_a) in 1/mm —
#: the most-used rows of pbrt's GetMediumScatteringProperties catalog
#: (src/core/medium.cpp). Others fall back to explicit parameters.
_SSS_PRESETS = {
    "Skimmilk": ([0.70, 1.22, 1.90], [0.0014, 0.0025, 0.0142]),
    "Wholemilk": ([2.55, 3.21, 3.77], [0.0011, 0.0024, 0.014]),
    "Skin1": ([0.74, 0.88, 1.01], [0.032, 0.17, 0.48]),
    "Skin2": ([1.09, 1.59, 1.79], [0.013, 0.070, 0.145]),
    "Marble": ([2.19, 2.62, 3.00], [0.0021, 0.0041, 0.0071]),
    "Ketchup": ([0.18, 0.07, 0.03], [0.061, 0.97, 1.45]),
    "Cream": ([7.38, 5.47, 3.15], [0.0002, 0.0028, 0.0163]),
    "Spectralon": ([11.6, 20.4, 14.9], [0.00, 0.00, 0.00]),
}


def lower_materials(mat_records: List, tex_registry,
                    scene_dir: str = ".") -> Dict[str, np.ndarray]:
    """MaterialRecords -> SoA table. tex_registry assigns ids to
    non-constant textures (returns -1 for constants).

    Mix materials (mixmat.cpp) expand here: each mix row's two
    sub-materials are appended as REAL rows of the same table and the
    mix row records (mix_a, mix_b, mix_amt). Shading resolves a mix
    lane to ONE sub-row by a sampler draw before the parameter gather
    (bxdf.resolve_mix) — the one-sample estimator of the scaled BSDF
    union, exact for scalar `amount` (see resolve_mix docstring).
    Nested mixes expand recursively (resolution loops a static 4 deep)."""
    mat_records = list(mat_records)
    mix_sub: Dict[int, Tuple[int, int]] = {}
    i_scan = 0
    while i_scan < len(mat_records):
        rec = mat_records[i_scan]
        if rec.type == "mix":
            m1 = rec.params.get("material1")
            m2 = rec.params.get("material2")
            if m1 is not None and m2 is not None:
                ia = len(mat_records)
                mat_records.append(m1)
                ib = len(mat_records)
                mat_records.append(m2)
                mix_sub[i_scan] = (ia, ib)
        i_scan += 1
    m = len(mat_records)
    tab = {
        "type": np.zeros(m, np.int32),
        "kd": np.zeros((m, 3), np.float32),
        "ks": np.zeros((m, 3), np.float32),
        "kr": np.zeros((m, 3), np.float32),
        "kt": np.zeros((m, 3), np.float32),
        "eta": np.ones((m, 3), np.float32),
        "k": np.zeros((m, 3), np.float32),
        "rough_u": np.zeros(m, np.float32),
        "rough_v": np.zeros(m, np.float32),
        "sigma": np.zeros(m, np.float32),
        "opacity": np.ones((m, 3), np.float32),
        "remap": np.ones(m, np.int32),
        "mix_a": np.full(m, -1, np.int32),
        "mix_b": np.full(m, -1, np.int32),
        "mix_amt": np.full(m, 0.5, np.float32),
        "sub_id": np.full(m, -1, np.int32),
        "kd_tex": np.full(m, -1, np.int32),
        "ks_tex": np.full(m, -1, np.int32),
        "sigma_tex": np.full(m, -1, np.int32),
        "rough_tex": np.full(m, -1, np.int32),
        "opacity_tex": np.full(m, -1, np.int32),
        "bump_tex": np.full(m, -1, np.int32),
    }

    #: (sigma_s, sigma_a, g, eta) per subsurface material, in sub_id
    #: order; compile_scene bakes these into the device BSSRDF table
    sss_rows: List[tuple] = []

    def fold_spec(rec, key, default, slot, tex_slot=None, i=0):
        node = rec.params.get(key)
        val, folded = _fold_const(node, default)
        if not folded:
            tid = tex_registry(node)
            if tex_slot is not None:
                tab[tex_slot][i] = tid
            val, _ = _fold_const(None, default)  # fall back to default under texture
            # average color as fallback beneath the texture lookup
            if tid < 0:
                Warning(f"texture for {key} not representable; using default")
        tab[slot][i] = _rgb(val)
        return folded

    def fold_f(rec, key, default, slot, tex_slot=None, i=0):
        node = rec.params.get(key)
        val, folded = _fold_const(node, default)
        if not folded:
            tid = tex_registry(node)
            if tex_slot is not None:
                tab[tex_slot][i] = tid
            val = default
        arr = np.asarray(val, np.float64).reshape(-1)
        tab[slot][i] = float(arr.mean())
        return folded

    for i, rec in enumerate(mat_records):
        t = rec.type
        tab["type"][i] = _MAT_ENUM.get(t, MAT_MATTE)
        p = rec.params
        if t == "matte":
            fold_spec(rec, "Kd", 0.5, "kd", "kd_tex", i)
            fold_f(rec, "sigma", 0.0, "sigma", "sigma_tex", i)
        elif t == "plastic":
            fold_spec(rec, "Kd", 0.25, "kd", "kd_tex", i)
            fold_spec(rec, "Ks", 0.25, "ks", "ks_tex", i)
            fold_f(rec, "roughness", 0.1, "rough_u", "rough_tex", i)
            tab["rough_v"][i] = tab["rough_u"][i]
            tab["remap"][i] = int(p.get("remaproughness", True))
        elif t == "metal":
            fold_spec(rec, "eta", 1.0, "eta", None, i)
            fold_spec(rec, "k", 1.0, "k", None, i)
            fold_f(rec, "roughness", 0.01, "rough_u", "rough_tex", i)
            tab["rough_v"][i] = tab["rough_u"][i]
            if p.get("uroughness") is not None:
                fold_f(rec, "uroughness", 0.01, "rough_u", None, i)
            if p.get("vroughness") is not None:
                fold_f(rec, "vroughness", 0.01, "rough_v", None, i)
            tab["remap"][i] = int(p.get("remaproughness", True))
        elif t == "glass":
            fold_spec(rec, "Kr", 1.0, "kr", None, i)
            fold_spec(rec, "Kt", 1.0, "kt", None, i)
            fold_f(rec, "eta", 1.5, "eta", None, i)
            # glass.cpp: nonzero uroughness/vroughness selects the
            # microfacet reflection/transmission lobes (rough glass).
            # vroughness defaults to 0 INDEPENDENTLY of uroughness (a
            # scene giving only uroughness is anisotropic under pbrt)
            fold_f(rec, "uroughness", 0.0, "rough_u", "rough_tex", i)
            fold_f(rec, "vroughness", 0.0, "rough_v", None, i)
            tab["remap"][i] = int(p.get("remaproughness", True))
            tab["eta"][i] = tab["eta"][i][:1].repeat(3)
        elif t == "mirror":
            fold_spec(rec, "Kr", 0.9, "kr", None, i)
        elif t == "uber":
            fold_spec(rec, "Kd", 0.25, "kd", "kd_tex", i)
            fold_spec(rec, "Ks", 0.25, "ks", "ks_tex", i)
            fold_spec(rec, "Kr", 0.0, "kr", None, i)
            fold_spec(rec, "Kt", 0.0, "kt", None, i)
            fold_f(rec, "roughness", 0.1, "rough_u", "rough_tex", i)
            tab["rough_v"][i] = tab["rough_u"][i]
            if p.get("uroughness") is not None:
                fold_f(rec, "uroughness", 0.1, "rough_u", None, i)
            if p.get("vroughness") is not None:
                fold_f(rec, "vroughness", 0.1, "rough_v", None, i)
            fold_f(rec, "eta", 1.5, "eta", None, i)
            tab["eta"][i] = tab["eta"][i][:1].repeat(3)
            fold_spec(rec, "opacity", 1.0, "opacity", "opacity_tex", i)
            tab["remap"][i] = int(p.get("remaproughness", True))
        elif t == "substrate":
            fold_spec(rec, "Kd", 0.5, "kd", "kd_tex", i)
            fold_spec(rec, "Ks", 0.5, "ks", "ks_tex", i)
            fold_f(rec, "uroughness", 0.1, "rough_u", "rough_tex", i)
            fold_f(rec, "vroughness", 0.1, "rough_v", None, i)
            tab["remap"][i] = int(p.get("remaproughness", True))
        elif t == "translucent":
            fold_spec(rec, "Kd", 0.25, "kd", "kd_tex", i)
            fold_spec(rec, "Ks", 0.25, "ks", "ks_tex", i)
            fold_spec(rec, "reflect", 0.5, "kr", None, i)
            fold_spec(rec, "transmit", 0.5, "kt", None, i)
            fold_f(rec, "roughness", 0.1, "rough_u", "rough_tex", i)
            tab["rough_v"][i] = tab["rough_u"][i]
            tab["remap"][i] = int(p.get("remaproughness", True))
        elif t == "disney":
            # full Disney 2015 lobe set (disney.cpp): parameters land in
            # dedicated d_* slots added lazily below; the shared slots
            # carry color/rough/eta for the generic machinery
            _ensure_disney_slots(tab, m)
            fold_spec(rec, "color", 0.5, "kd", "kd_tex", i)
            fold_f(rec, "roughness", 0.5, "rough_u", "rough_tex", i)
            tab["rough_v"][i] = tab["rough_u"][i]
            fold_f(rec, "eta", 1.5, "eta", None, i)
            tab["eta"][i] = tab["eta"][i][:1].repeat(3)
            tab["remap"][i] = 0
            for key, slot, dflt in (
                ("metallic", "d_metallic", 0.0),
                ("speculartint", "d_spectint", 0.0),
                ("anisotropic", "d_aniso", 0.0),
                ("sheen", "d_sheen", 0.0),
                ("sheentint", "d_sheentint", 0.5),
                ("clearcoat", "d_clearcoat", 0.0),
                ("clearcoatgloss", "d_ccgloss", 1.0),
                ("spectrans", "d_strans", 0.0),
                ("flatness", "d_flat", 0.0),
                ("difftrans", "d_dtrans", 1.0),
            ):
                fold_f(rec, key, dflt, slot, None, i)
            thin, _ = _fold_const(p.get("thin"), False)
            tab["d_thin"][i] = 1 if thin else 0
            sd, _ = _fold_const(p.get("scatterdistance"), 0.0)
            if np.any(np.asarray(sd, np.float64) > 0):
                Warning(
                    "disney scatterdistance > 0 (subsurface) is not "
                    "supported; shading as the solid Disney BSDF"
                )
        elif t == "hair":
            # full Chiang/pbrt HairBSDF (hair.cpp): sigma_a resolution
            # order matches HairMaterial::ComputeScatteringFunctions
            _ensure_hair_slots(tab, m)
            bn, _ = _fold_const(p.get("beta_n"), 0.3)
            bn = float(np.asarray(bn, np.float64).reshape(-1).mean())
            if p.get("sigma_a") is not None:
                sa, _ = _fold_const(p.get("sigma_a"), 1.3)
                sa = _rgb(sa)
            elif p.get("color") is not None:
                col, _ = _fold_const(p.get("color"), 0.5)
                sa = _hair_sigma_a_from_reflectance(_rgb(col), bn)
            else:
                eu, _ = _fold_const(p.get("eumelanin"), 1.3)
                ph, _ = _fold_const(p.get("pheomelanin"), 0.0)
                eu = float(np.asarray(eu, np.float64).reshape(-1).mean())
                ph = float(np.asarray(ph, np.float64).reshape(-1).mean())
                # HairMaterial: eumelanin/pheomelanin absorption spectra
                sa = eu * np.array([0.419, 0.697, 1.37]) + ph * np.array(
                    [0.187, 0.4, 1.05]
                )
            tab["h_sigma_a"][i] = np.asarray(sa, np.float32)
            fold_f(rec, "beta_m", 0.3, "h_beta_m", None, i)
            tab["h_beta_n"][i] = bn
            fold_f(rec, "alpha", 2.0, "h_alpha", None, i)
            fold_f(rec, "eta", 1.55, "eta", None, i)
            tab["eta"][i] = tab["eta"][i][:1].repeat(3)
            # fallback color for integrators that only store diffuse
            tab["kd"][i] = np.exp(-np.asarray(sa, np.float64) * 0.5)
        elif t == "fourier":
            # real tabulated FourierBSDF when the .bsdf file loads
            # (core/fourierbsdf.py); loud diffuse fallback otherwise
            fn, _ = _fold_const(p.get("bsdffile"), "")
            prev = tab.get("_fourier")
            tab_obj = None
            if fn and prev is not None and prev[1] == str(fn):
                tab_obj = prev[0]  # same file: reuse, skip the re-read
            elif fn and prev is not None:
                Warning(
                    "multiple distinct fourier bsdffiles in one scene "
                    "are not supported; reusing the first table"
                )
                tab_obj = prev[0]
            elif fn:
                from tpu_pbrt.core.fourierbsdf import read_bsdf_file
                from tpu_pbrt.utils.fileutil import resolve_filename

                try:
                    tab_obj = read_bsdf_file(resolve_filename(str(fn), scene_dir))
                    tab["_fourier"] = (tab_obj, str(fn))
                except Exception as e:  # noqa: BLE001
                    Warning(f'fourier: could not read "{fn}" ({e}); '
                            "SUBSTITUTING a 0.5 diffuse BSDF")
            else:
                Warning('fourier material without "bsdffile"; '
                        "SUBSTITUTING a 0.5 diffuse BSDF")
            if tab_obj is None:
                tab["type"][i] = MAT_MATTE
            tab["kd"][i] = 0.5
        elif t in ("subsurface", "kdsubsurface"):
            # real BSSRDF transport (core/bssrdf.py): the surface BSDF
            # is the smooth Fresnel interface (glass kr/kt — gather_mat
            # remaps the type); the medium's beam-diffusion profile is
            # baked per channel below and the path integrator runs the
            # Sample_Sp probe wave (subsurface.cpp / bssrdf.cpp)
            fold_spec(rec, "Kr", 1.0, "kr", None, i)
            fold_spec(rec, "Kt", 1.0, "kt", None, i)
            fold_f(rec, "eta", 1.33, "eta", None, i)
            tab["eta"][i] = tab["eta"][i][:1].repeat(3)
            eta_v = float(tab["eta"][i][0])
            g_v = 0.0
            if t == "subsurface":
                g_v = float(_fold_const(p.get("g"), 0.0)[0])
                preset = str(p.get("preset") or "")
                if preset and preset in _SSS_PRESETS:
                    sig_sp, sig_a = (
                        np.asarray(v, np.float64)
                        for v in _SSS_PRESETS[preset]
                    )
                elif preset:
                    Warning(
                        f'subsurface: unknown medium preset "{preset}"; '
                        "using the sigma_a/sigma_prime_s parameters"
                    )
                    preset = ""
                if not preset:
                    sa, fold_a = _fold_const(
                        p.get("sigma_a"), np.array([0.0011, 0.0024, 0.014])
                    )
                    ss_, fold_s = _fold_const(
                        p.get("sigma_s"), np.array([2.55, 3.21, 3.77])
                    )
                    if not (fold_a and fold_s):
                        Warning(
                            "subsurface: textured sigma_a/sigma_prime_s "
                            "are not supported (the diffusion profile "
                            "bakes per material); using constants"
                        )
                    sig_a = _rgb(sa).astype(np.float64)
                    sig_sp = _rgb(ss_).astype(np.float64)
                scale = float(_fold_const(p.get("scale"), 1.0)[0])
                sig_a = sig_a * scale
                sigma_s = sig_sp * scale / max(1.0 - g_v, 1e-3)
            else:
                from tpu_pbrt.core.bssrdf import subsurface_from_diffuse

                kd_v, _ = _fold_const(p.get("Kd"), 0.5)
                mfp_v, _ = _fold_const(p.get("mfp"), 1.0)
                sigma_s, sig_a = subsurface_from_diffuse(
                    _rgb(kd_v), _rgb(mfp_v), g_v, eta_v
                )
            ur, _ = _fold_const(p.get("uroughness"), 0.0)
            if np.max(np.asarray(ur, np.float64)) > 0:
                Warning(
                    "subsurface: rough interface not supported; using "
                    "the smooth specular interface"
                )
            tab["sub_id"][i] = len(sss_rows)
            sss_rows.append((sigma_s, sig_a, g_v, eta_v))
            # fallback albedo for integrators without the probe wave
            # (bdpt/sppm/mlt shade the interface only — warned at render)
            tab["kd"][i] = 0.5
        elif t == "mix":
            # true MixMaterial (mixmat.cpp): sub-materials are rows
            # ia/ib of this same table (expanded in the pre-pass);
            # shading resolves the lane stochastically by `amount`
            # before the gather (bxdf.resolve_mix). The row's own
            # shading params are a diffuse blend FALLBACK used only
            # past the static nesting-depth limit.
            amt, folded = _fold_const(p.get("amount"), 0.5)
            a = _rgb(amt)
            if not folded:
                Warning(
                    "mix: textured `amount` is not supported; using "
                    "its constant fallback for the selection probability"
                )
            if a.min() != a.max():
                Warning(
                    "mix: colored `amount` selects by its channel MEAN "
                    "(per-channel mix weights are approximated)"
                )
            if i in mix_sub:
                ia, ib = mix_sub[i]
                tab["mix_a"][i] = ia
                tab["mix_b"][i] = ib
                tab["mix_amt"][i] = float(np.clip(a.mean(), 0.0, 1.0))
            tab["type"][i] = MAT_MATTE
            m1 = p.get("material1")
            m2 = p.get("material2")
            kd1, _ = _fold_const(m1.params.get("Kd") if m1 else None, 0.5)
            kd2, _ = _fold_const(m2.params.get("Kd") if m2 else None, 0.5)
            tab["kd"][i] = _rgb(kd1) * a + _rgb(kd2) * (1 - a)
        # "none" keeps zeros (passthrough)
    if not (tab["mix_a"] >= 0).any():
        # mix-free scene: drop the columns so resolve_mix is a static
        # no-op in every traced program (key presence IS the flag)
        del tab["mix_a"], tab["mix_b"], tab["mix_amt"]
    if sss_rows:
        tab["_sss_rows"] = sss_rows
    else:
        del tab["sub_id"]
    return tab


# -------------------------------------------------------------------------
# The compile pass
# -------------------------------------------------------------------------

def _geometric_normals(verts: np.ndarray) -> np.ndarray:
    e1 = verts[:, 1] - verts[:, 0]
    e2 = verts[:, 2] - verts[:, 0]
    n = np.cross(e1, e2)
    ln = np.linalg.norm(n, axis=-1, keepdims=True)
    n = n / np.maximum(ln, 1e-20)
    return np.repeat(n[:, None, :], 3, axis=1)


def packed_id_base(n_light_rows: int) -> int:
    """The radix of `tri_sh16`'s packed id column, mat * base + light + 1 as
    an exact float32: 4096 while the light ids fit under it, else the power
    of two above them. Static: the program reads it off the table's shape."""
    return 4096 if n_light_rows < 4095 else 1 << (n_light_rows + 1).bit_length()


def resident_bytes(dev) -> Dict[str, int]:
    """What each table of a compiled scene holds on its device, in bytes as
    the device lays it out (a minor dimension of 3 is padded to 4 there;
    `nbytes` where the backend does not say): `dev`'s entries by name, the
    stream pack's by field."""
    import jax

    def size(tree) -> int:
        return sum(
            int(leaf.on_device_size_in_bytes()) if hasattr(leaf, "on_device_size_in_bytes")
            else int(getattr(leaf, "nbytes", 0))
            for leaf in jax.tree.leaves(tree)
        )

    tables = {k: v for k, v in dev.items() if k != "tstream"}
    if "tstream" in dev:
        tables |= {f"tstream.{f}": v for f, v in dev["tstream"]._asdict().items()}
    return {k: n for k, n in ((k, size(v)) for k, v in tables.items()) if n}


def compile_scene(api) -> CompiledScene:
    ro = api.render_options
    opts = api.options

    # -- film / filter / camera / sampler --------------------------------
    with TRACE.span("scene/camera"):  # film, filter, camera, sampler
        filt = make_filter(ro.filter_name, ro.filter_params)
        film = make_film(ro.film_name, ro.film_params, filt, opts)
        camera = make_camera(
            ro.camera_name,
            ro.camera_params,
            ro.camera_to_world[0],
            film.full_resolution,
            (
                ro.camera_params.find_one_float("shutteropen", 0.0),
                ro.camera_params.find_one_float("shutterclose", 1.0),
            ),
            film_diag=film.diagonal,
            scene_dir=getattr(api, "scene_dir", "."),
        )
        spp = ro.sampler_params.find_one_int("pixelsamples", 16)
        if getattr(opts, "quick_render", False):
            spp = max(1, spp // 4)
        sampler = SamplerSpec(ro.sampler_name, spp, ro.sampler_params)

    # -- gather shapes (instances expanded) ------------------------------
    with TRACE.span("scene/shapes"):  # tessellate, to world space; scene/ply_read inside
        shape_list = list(ro.shapes)
        for use in ro.instance_uses:
            for rec in ro.instances.get(use.name, []):
                import copy as _copy

                r2 = _copy.copy(rec)
                r2.object_to_world = type(rec.object_to_world)(
                    [use.instance_to_world[i] * rec.object_to_world[i] for i in range(2)]
                )
                shape_list.append(r2)

        all_verts, all_normals, all_uvs = [], [], []
        all_verts1 = []
        any_motion = False
        all_mat, all_light = [], []
        mat_records: List = []
        mat_index: Dict[int, int] = {}
        light_rows: List[Dict[str, np.ndarray]] = []  # `_light_rows` blocks, in row order
        n_light_rows = 0
        #: shared image atlas for goniometric/projection light maps
        light_atlas_chunks: List[np.ndarray] = []
        shape_tri_counts: List = []  # (ShapeRecord, n_tris) for medium interfaces

        def mat_id_for(mrec):
            if mrec is None:
                from tpu_pbrt.scene.api import MaterialRecord

                mrec = MaterialRecord("none", {})
            key = id(mrec)
            if key not in mat_index:
                mat_index[key] = len(mat_records)
                mat_records.append(mrec)
            return mat_index[key]

        for rec in shape_list:
            tess = tessellate_shape(rec)
            if tess is None:
                continue
            verts, normals, uvs = tess
            o2w = rec.object_to_world[0]
            o2w1 = rec.object_to_world[1]
            wverts = o2w.apply_point(verts.reshape(-1, 3)).reshape(-1, 3, 3)
            # shutter-end keyframe (AnimatedTransform endpoint baking: verts
            # interpolate LINEARLY per ray time — transform.cpp's decompose+
            # slerp differs for large rotations; documented deviation)
            if not np.allclose(o2w.m, o2w1.m):
                wverts1 = o2w1.apply_point(verts.reshape(-1, 3)).reshape(-1, 3, 3)
                any_motion = True
            else:
                wverts1 = wverts
            if normals is not None:
                wn = o2w.apply_normal(normals.reshape(-1, 3)).reshape(-1, 3, 3)
                ln = np.linalg.norm(wn, axis=-1, keepdims=True)
                wn = wn / np.maximum(ln, 1e-20)
            else:
                wn = _geometric_normals(wverts)
            if rec.reverse_orientation ^ o2w.swaps_handedness():
                wn = -wn
            if uvs is None:
                uvs = np.zeros((len(wverts), 3, 2))
                uvs[:, 1, 0] = 1.0
                uvs[:, 2] = [1.0, 1.0]
            mid = mat_id_for(rec.material)
            n_t = len(wverts)
            base = sum(len(v) for v in all_verts)
            shape_tri_counts.append((rec, n_t))
            all_verts.append(wverts)
            all_verts1.append(wverts1)
            all_normals.append(wn)
            all_uvs.append(uvs)
            all_mat.append(np.full(n_t, mid, np.int32))
            lids = np.full(n_t, -1, np.int32)
            if rec.area_light is not None:
                # one DiffuseAreaLight per triangle (pbrt MakeShapes semantics)
                L = _rgb(rec.area_light.find_one_spectrum("L", np.array([1.0, 1.0, 1.0])))
                sc = _rgb(rec.area_light.find_one_spectrum("scale", np.array([1.0, 1.0, 1.0])))
                two = rec.area_light.find_one_bool("twosided", False)
                e1 = wverts[:, 1] - wverts[:, 0]
                e2 = wverts[:, 2] - wverts[:, 0]
                areas = 0.5 * np.linalg.norm(np.cross(e1, e2), axis=-1)
                lids[:] = n_light_rows + np.arange(n_t)
                light_rows.append(_light_rows(
                    n_t, type=LIGHT_AREA, L=L * sc, tri=base + np.arange(n_t),
                    twosided=int(two), area=areas,
                ))
                n_light_rows += n_t
            all_light.append(lids)

    # motion blur is active only when something moves AND the camera
    # shutter is open for a nonzero interval
    with TRACE.span("scene/assemble"):  # one table a column, world and triangle bounds
        shutter = (
            ro.camera_params.find_one_float("shutteropen", 0.0),
            ro.camera_params.find_one_float("shutterclose", 1.0),
        )
        any_motion = any_motion and shutter[1] > shutter[0]
        if all_verts:
            verts = np.concatenate(all_verts).astype(np.float64)
            verts1 = np.concatenate(all_verts1).astype(np.float64) if any_motion else None
            normals = np.concatenate(all_normals).astype(np.float32)
            uvs = np.concatenate(all_uvs).astype(np.float32)
            mat_ids = np.concatenate(all_mat)
            light_ids = np.concatenate(all_light)
        else:
            # no geometry: a degenerate far-away triangle keeps shapes static
            verts = np.full((1, 3, 3), 1e30)
            verts1 = None
            any_motion = False
            normals = np.zeros((1, 3, 3), np.float32)
            normals[:, :, 2] = 1.0
            uvs = np.zeros((1, 3, 2), np.float32)
            mat_ids = np.zeros(1, np.int32)
            light_ids = np.full(1, -1, np.int32)
            from tpu_pbrt.scene.api import MaterialRecord

            mat_records.append(MaterialRecord("none", {}))

        # -- world bounds (union over the shutter when anything moves) -------
        vb = verts if verts1 is None else np.concatenate([verts, verts1])
        finite = np.abs(vb).max(axis=(1, 2)) < 1e29
        if finite.any():
            wmin = vb[finite].min(axis=(0, 1))
            wmax = vb[finite].max(axis=(0, 1))
        else:
            wmin = np.full(3, -1.0)
            wmax = np.full(3, 1.0)
        wcenter = 0.5 * (wmin + wmax)
        wradius = float(np.linalg.norm(wmax - wcenter)) + 1e-6

        # -- BVH (per-tri bounds = union over the two keyframes) -------------
        bmin, bmax = triangle_bounds(verts)
        if verts1 is not None:
            bmin1, bmax1 = triangle_bounds(verts1)
            bmin = np.minimum(bmin, bmin1)
            bmax = np.maximum(bmax, bmax1)
    with TRACE.span("accel/sah_build", tris=len(verts)):
        bvh = build_bvh(bmin, bmax, method=ro.accelerator_params.find_one_string("splitmethod", "auto")
                        if ro.accelerator_name == "bvh" else "auto")
    with TRACE.span("scene/reorder"):  # every table into leaf order
        order = bvh.prim_order
        verts = verts[order]
        if verts1 is not None:
            verts1 = verts1[order]
        normals = normals[order]
        uvs = uvs[order]
        mat_ids = mat_ids[order]
        light_ids = light_ids[order]
        # area-light rows reference triangle ids -> remap to leaf order
        inv_order = np.empty_like(order)
        inv_order[order] = np.arange(len(order))
        for rows in light_rows:  # area-light blocks only, so far
            rows["tri"] = inv_order[rows["tri"]]

    # -- non-area lights -------------------------------------------------
    with TRACE.span("scene/lights"):  # lights, media, their tables and distributions
        envmap = None
        env_distr = None
        has_envmap = False
        env_w2l = np.eye(4, dtype=np.float32)
        for lrec in ro.lights:
            l2w = lrec.light_to_world
            p = lrec.params
            sc = _rgb(p.find_one_spectrum("scale", np.array([1.0, 1.0, 1.0])))
            if lrec.type == "point":
                I = _rgb(p.find_one_spectrum("I", np.array([1.0, 1.0, 1.0]))) * sc
                pos = l2w.apply_point(p.find_one_point3("from", [0.0, 0.0, 0.0]))
                light_rows.append(_light_rows(type=LIGHT_POINT, p=pos, L=I))
            elif lrec.type == "spot":
                I = _rgb(p.find_one_spectrum("I", np.array([1.0, 1.0, 1.0]))) * sc
                cone = p.find_one_float("coneangle", 30.0)
                delta = p.find_one_float("conedeltaangle", 5.0)
                frm = np.asarray(p.find_one_point3("from", [0, 0, 0]), np.float64)
                to = np.asarray(p.find_one_point3("to", [0, 0, 1]), np.float64)
                pos = l2w.apply_point(frm)
                d = l2w.apply_point(to) - pos
                d = d / max(np.linalg.norm(d), 1e-20)
                light_rows.append(_light_rows(
                    type=LIGHT_SPOT, p=pos, L=I, dir=d,
                    cos0=math.cos(math.radians(cone - delta)),  # falloff start
                    cos1=math.cos(math.radians(cone)),  # total width
                ))
            elif lrec.type == "distant":
                L = _rgb(p.find_one_spectrum("L", np.array([1.0, 1.0, 1.0]))) * sc
                frm = np.asarray(p.find_one_point3("from", [0, 0, 0]), np.float64)
                to = np.asarray(p.find_one_point3("to", [0, 0, 1]), np.float64)
                d = l2w.apply_vector(frm - to)
                d = d / max(np.linalg.norm(d), 1e-20)  # direction TOWARD light
                light_rows.append(_light_rows(type=LIGHT_DISTANT, L=L, dir=d))
            elif lrec.type in ("infinite", "exinfinite"):
                L = _rgb(p.find_one_spectrum("L", np.array([1.0, 1.0, 1.0]))) * sc
                fn = p.find_one_string("mapname", "")
                w2l = np.asarray(l2w.inverse().m, np.float32)
                if fn:
                    from tpu_pbrt.utils import imageio

                    path = resolve_filename(fn, lrec.scene_dir)
                    try:
                        img = imageio.read_image(path) * L[None, None]
                        envmap = img.astype(np.float32)
                        has_envmap = True
                    except Exception as e:  # noqa: BLE001
                        Warning(f'could not read environment map "{path}": {e}; using constant')
                        envmap = np.full((4, 8, 3), L, np.float32)
                        has_envmap = True
                else:
                    envmap = np.full((4, 8, 3), L, np.float32)
                    has_envmap = True
                # importance distribution over luminance * sin(theta)
                hgt, wdt = envmap.shape[:2]
                lum = luminance(envmap)
                theta = (np.arange(hgt) + 0.5) / hgt * np.pi
                env_distr = Distribution2D.build(lum * np.sin(theta)[:, None])
                light_rows.append(_light_rows(type=LIGHT_INFINITE, p=wcenter, L=np.ones(3)))
                # store world-to-light for map lookups
                env_w2l = w2l
            elif lrec.type in ("projection", "goniometric"):
                # goniometric.cpp / projection.cpp: a delta-position light whose
                # angular intensity is modulated by an image (goniophotometric
                # diagram in spherical coords / projected texture inside a fov
                # frustum). The image goes into the shared light atlas; the
                # world-to-light rotation rides the row.
                I = _rgb(p.find_one_spectrum("I", np.array([1.0, 1.0, 1.0]))) * sc
                pos = l2w.apply_point([0.0, 0.0, 0.0])
                fn = p.find_one_string("mapname", "")
                img = None
                if fn:
                    from tpu_pbrt.utils import imageio as _iio

                    try:
                        img = np.asarray(
                            _iio.read_image(resolve_filename(fn, lrec.scene_dir)),
                            np.float32,
                        )
                    except Exception as e:  # noqa: BLE001
                        Warning(f'could not read light map "{fn}": {e}; using constant')
                if img is None:
                    img = np.ones((1, 1, 3), np.float32)
                if img.ndim == 2:
                    img = np.repeat(img[..., None], 3, -1)
                img = np.ascontiguousarray(img[..., :3], np.float32)
                off = sum(ch.shape[0] for ch in light_atlas_chunks)
                light_atlas_chunks.append(img.reshape(-1, 3))
                w2l_rot = np.asarray(l2w.inverse().m, np.float64)[:3, :3]
                if lrec.type == "goniometric":
                    light_rows.append(_light_rows(
                        type=LIGHT_GONIO, p=pos, L=I, w2l=w2l_rot.reshape(-1),
                        img=[off, img.shape[1], img.shape[0]],
                    ))
                else:
                    fov = p.find_one_float("fov", 45.0)
                    # projection.cpp: screen window from aspect; the map covers
                    # the [-1,1] (short axis) frustum at tan(fov/2)
                    aspect = img.shape[1] / img.shape[0]
                    tan_half = math.tan(math.radians(fov) / 2.0)
                    light_rows.append(_light_rows(
                        type=LIGHT_PROJECTION, p=pos, L=I, cos0=tan_half, cos1=aspect,
                        w2l=w2l_rot.reshape(-1), img=[off, img.shape[1], img.shape[0]],
                    ))
            else:
                Warning(f'LightSource "{lrec.type}" unknown.')

        # -- media (medium.cpp / media/{homogeneous,grid}.cpp lowering) ------
        from tpu_pbrt.core.media import (
            MEDIUM_GRID,
            MEDIUM_HOMOGENEOUS,
            MEDIUM_PRESETS,
            MediumTable,
            empty_medium_table,
        )

        medium_ids: Dict[str, int] = {"": -1}
        med_rows = []
        grid_density_arr = None
        grid_w2m = np.eye(4, dtype=np.float32)
        sigma_t_max = 0.0
        for mname, mrec in ro.named_media.items():
            p = mrec.params
            scale_m = p.find_one_float("scale", 1.0)
            g_m = p.find_one_float("g", 0.0)
            preset = p.find_one_string("preset", "")
            sig_a_d = np.array([0.0011, 0.0024, 0.014])
            sig_s_d = np.array([2.55, 3.21, 3.77])
            if preset:
                if preset in MEDIUM_PRESETS:
                    sig_s_d, sig_a_d = MEDIUM_PRESETS[preset]
                else:
                    Warning(f'Material preset "{preset}" not found; using defaults')
            sig_a = _rgb(p.find_one_spectrum("sigma_a", sig_a_d)) * scale_m
            sig_s = _rgb(p.find_one_spectrum("sigma_s", sig_s_d)) * scale_m
            if mrec.type == "homogeneous":
                med_rows.append(dict(type=MEDIUM_HOMOGENEOUS, sa=sig_a, ss=sig_s, g=g_m, grid=-1))
            elif mrec.type == "heterogeneous" or mrec.type == "grid":
                nx = p.find_one_int("nx", 1)
                ny = p.find_one_int("ny", 1)
                nz = p.find_one_int("nz", 1)
                dvals = p.find_float("density")
                if dvals is None or len(dvals) != nx * ny * nz:
                    Error(f'GridDensityMedium requires nx*ny*nz "density" values')
                if grid_density_arr is not None:
                    Warning("multiple grid media: only one density grid supported; last wins")
                grid_density_arr = np.asarray(dvals, np.float32).reshape(nz, ny, nx)
                # pbrt maps medium space [0,1]^3 through p0/p2 bounds if given
                p0 = np.asarray(p.find_one_point3("p0", [0.0, 0.0, 0.0]))
                p1 = np.asarray(p.find_one_point3("p1", [1.0, 1.0, 1.0]))
                m2w = mrec.medium_to_world.m @ np.block(
                    [[np.diag(p1 - p0), (p0)[:, None]], [np.zeros((1, 3)), np.ones((1, 1))]]
                )
                grid_w2m = np.linalg.inv(m2w).astype(np.float32)
                sigma_t_max = float((sig_a + sig_s).max() * grid_density_arr.max())
                med_rows.append(dict(type=MEDIUM_GRID, sa=sig_a, ss=sig_s, g=g_m, grid=0))
            else:
                Warning(f'Medium "{mrec.type}" unknown; ignored.')
                med_rows.append(dict(type=MEDIUM_HOMOGENEOUS, sa=sig_a * 0, ss=sig_s * 0, g=0.0, grid=-1))
            medium_ids[mname] = len(med_rows) - 1

        if med_rows:
            medium_table = MediumTable(
                mtype=jnp.asarray([r["type"] for r in med_rows], jnp.int32),
                sigma_a=jnp.asarray(np.array([r["sa"] for r in med_rows]), jnp.float32),
                sigma_s=jnp.asarray(np.array([r["ss"] for r in med_rows]), jnp.float32),
                g=jnp.asarray([r["g"] for r in med_rows], jnp.float32),
                grid_id=jnp.asarray([r["grid"] for r in med_rows], jnp.int32),
                density=jnp.asarray(
                    grid_density_arr if grid_density_arr is not None else np.zeros((1, 1, 1), np.float32)
                ),
                world_to_medium=jnp.asarray(grid_w2m, jnp.float32),
                sigma_t_max=jnp.float32(sigma_t_max),
            )
        else:
            medium_table = empty_medium_table()

        # per-triangle medium interface ids (primitive.h MediumInterface)
        med_in = np.full(len(verts), -1, np.int32)
        med_out = np.full(len(verts), -1, np.int32)
        tri_base = 0
        for rec, n_t in shape_tri_counts:
            med_in[tri_base : tri_base + n_t] = medium_ids.get(rec.inside_medium, -1)
            med_out[tri_base : tri_base + n_t] = medium_ids.get(rec.outside_medium, -1)
            tri_base += n_t
        if len(order) == len(med_in):
            med_in = med_in[order]
            med_out = med_out[order]
        camera_medium_id = medium_ids.get(ro.camera_medium, -1)

        n_lights = sum(len(rows["type"]) for rows in light_rows)
        if n_lights == 0:
            Warning("No light sources defined in scene; rendering a black image.")
            light_rows.append(_light_rows(type=LIGHT_POINT))
        # the table, one array a column, float64 until `lt` casts it
        rows = {k: np.concatenate([r[k] for r in light_rows]) for k in LIGHT_COLUMNS}
        lt = {k: rows[k].astype(dtype) for k, (_, dtype) in LIGHT_COLUMNS.items()}
        light_atlas = (
            np.concatenate(light_atlas_chunks, 0)
            if light_atlas_chunks
            else np.zeros((1, 3), np.float32)
        )

        with TRACE.span("scene/light_distribution") as picked:
            # power-weighted light selection distribution (lightdistrib.cpp
            # PowerLightDistribution); used when integrator asks for "power"
            ltype = rows["type"]
            lum = luminance(rows["L"])
            is_area = ltype == LIGHT_AREA
            power = lum * 4 * np.pi
            power[is_area] = (
                lum[is_area] * rows["area"][is_area] * np.pi
                * np.where(rows["twosided"][is_area] != 0, 2.0, 1.0)
            )
            # the infinite row carries L=1 (radiance lives in the envmap,
            # already scaled by L); power must reflect the map's mean luminance
            env_lum = (
                float(np.mean(luminance(envmap.astype(np.float64)))) if envmap is not None else lum
            )
            power = np.where(ltype == LIGHT_INFINITE, env_lum * np.pi * wradius * wradius * 4, power)
            power = np.where(ltype == LIGHT_DISTANT, lum * np.pi * wradius * wradius, power)
            for i in np.flatnonzero((ltype == LIGHT_GONIO) | (ltype == LIGHT_PROJECTION)):
                off, iw, ih = (int(v) for v in rows["img"][i])
                mean_lum = float(
                    np.mean(luminance(light_atlas[off : off + iw * ih].astype(np.float64)))
                )
                power[i] = lum[i] * mean_lum * 4 * np.pi
            # one row has nothing to weigh: its pick is 1 at any power, and a
            # unit power keeps the light's scale (a map's, which a scene may
            # vary from frame to frame) out of the compiled constants
            unit = len(power) == 1 or power.sum() <= 0
            light_distr = Distribution1D.build(np.ones_like(power) if unit else power)

            # -- spatial light distribution (lightdistrib.cpp
            # SpatialLightDistribution): dense per-voxel CDFs, importance estimated
            # at voxel centers (center-point simplification of pbrt's 128-sample MC)
            spatial_distr = None
            strategy_asked = ro.integrator_params.find_one_string("lightsamplestrategy", "spatial")
            strategy_built = strategy_asked
            res = (8, 8, 8)
            n_voxels = res[0] * res[1] * res[2]
            L = len(ltype)
            table_bytes = 0
            # The dense table is voxels x light rows x 4 bytes (mesh area
            # lights emit one row per triangle; pbrt's lazy hash exists to
            # avoid exactly this): what bounds it is its BYTES, against
            # SPATIAL_TABLE_BUDGET_BYTES. Wherever another strategy is built
            # than the file asked for, a Warning says so and the span carries
            # both names. One light is one pick under every strategy
            # (upstream's CreateLightSampleDistribution answers "uniform"
            # there): power stands in and nothing is lost.
            if strategy_asked not in LIGHT_STRATEGIES:
                Warning(
                    f'Light sample distribution type "{strategy_asked}" unknown. Using "power".'
                )
                strategy_built = "power"
            elif strategy_asked == "spatial" and n_lights <= 1:
                strategy_built = "power"
            elif strategy_asked == "spatial" and n_voxels * L * 4 > SPATIAL_TABLE_BUDGET_BYTES:
                Warning(
                    f'lightsamplestrategy "spatial" over {L} light rows is a table of '
                    f"{n_voxels * L * 4} bytes, over the budget of {SPATIAL_TABLE_BUDGET_BYTES}: "
                    'sampling lights by "power" instead'
                )
                strategy_built = "power"
            elif strategy_asked == "spatial":
                lo_g = wmin - 1e-3
                hi_g = wmax + 1e-3
                cs_g = np.maximum((hi_g - lo_g) / np.asarray(res), 1e-6)
                gx, gy, gz = res
                ii, jj, kk = np.meshgrid(
                    np.arange(gx), np.arange(gy), np.arange(gz), indexing="ij"
                )
                centers = lo_g + (np.stack([ii, jj, kk], -1).reshape(-1, 3, order="F") + 0.5) * cs_g
                V = centers.shape[0]
                imp = np.zeros((V, L), np.float64)
                for i in np.flatnonzero(~is_area):
                    t = ltype[i]
                    if t in (LIGHT_POINT, LIGHT_SPOT, LIGHT_GONIO, LIGHT_PROJECTION):
                        d2 = np.maximum(((centers - rows["p"][i]) ** 2).sum(-1), 1e-6)
                        base = lum[i] / d2
                        if t == LIGHT_SPOT:
                            toc = centers - rows["p"][i]
                            toc /= np.maximum(np.linalg.norm(toc, axis=-1, keepdims=True), 1e-12)
                            cosw = toc @ rows["dir"][i]
                            c0, c1 = rows["cos0"][i], rows["cos1"][i]
                            base = base * np.clip((cosw - c1) / max(c0 - c1, 1e-6), 0.05, 1.0)
                        imp[:, i] = base
                    else:  # distant / infinite: position-independent
                        imp[:, i] = power[i] / max(power.sum(), 1e-12)
                # area lights vectorized: centroid distance falloff x luminance x
                # area (rows carry LEAF-ORDER tri ids; verts is leaf-ordered here)
                if is_area.any():
                    cent = np.asarray(verts, np.float64).mean(axis=1)[rows["tri"][is_area]]  # (A,3)
                    d2 = np.maximum(
                        ((centers[:, None, :] - cent[None, :, :]) ** 2).sum(-1), 1e-6
                    )  # (V, A)
                    imp[:, is_area] = lum[is_area] * rows["area"][is_area] / d2
                row_sum = imp.sum(-1, keepdims=True)
                imp = np.where(row_sum > 0, imp / np.maximum(row_sum, 1e-30), 1.0 / L)
                cdf = np.cumsum(imp, -1).astype(np.float32)
                cdf[:, -1] = 1.0
                table_bytes = cdf.nbytes
                from tpu_pbrt.core.lights_dev import SpatialLightDistribution

                spatial_distr = SpatialLightDistribution.build(
                    cdf, imp.mean(0).astype(np.float32), lo_g, 1.0 / cs_g, res
                )
            # the pick's plan: 4-bit levels over pivot tables, then binary steps
            levels, tail = spatial_distr.plan if spatial_distr is not None else (0, 0)
            pivots = spatial_distr.pivots if spatial_distr is not None else ()
            picked.args.update(
                strategy_asked=strategy_asked, strategy_built=strategy_built,
                light_rows=int(n_lights), voxels=n_voxels if spatial_distr is not None else 0,
                table_bytes=int(table_bytes), pick_levels=levels, pick_tail_steps=tail,
                pivot_bytes=sum(int(t.nbytes) for t in pivots),
            )

    # -- materials -------------------------------------------------------
    # non-constant textures lower to real device evaluators (VERDICT r3
    # #6): nodes are deduped by structure, compiled into per-texture jax
    # closures + one flat mip atlas by core/texture_eval.py
    with TRACE.span("scene/materials"):
        deferred_textures: List = []
        _tex_ids: Dict[str, int] = {}

        def tex_registry(node):
            key = repr(node)
            tid = _tex_ids.get(key)
            if tid is None:
                tid = len(deferred_textures)
                _tex_ids[key] = tid
                deferred_textures.append(node)
            return tid

        mtab = lower_materials(mat_records, tex_registry,
                               getattr(api, "scene_dir", "."))

        tex_eval = None
        tex_atlas = None
        tex_used = set()
        if deferred_textures:
            from tpu_pbrt.core.texture_eval import build_texture_table

            tex_atlas, tex_eval = build_texture_table(deferred_textures)
            for slot, name in (
                ("kd_tex", "kd"), ("ks_tex", "ks"), ("sigma_tex", "sigma"),
                ("rough_tex", "rough"), ("opacity_tex", "opacity"),
            ):
                if (mtab[slot] >= 0).any():
                    tex_used.add(name)
            if (mtab["bump_tex"] >= 0).any():
                Warning("bump textures are parsed but not applied (no shading-"
                        "normal perturbation yet)")

    # -- device upload ---------------------------------------------------
    # One acceleration structure only (VERDICT r1 weak #4: no duplicate
    # geometry in HBM). The stream (sort/compaction wavefront) tracer over
    # the two-level treelet BVH is the TPU-shaped default (accel/stream.py
    # — coherence-independent, sized for incoherent bounce waves); scenes
    # at or below BRUTE_MAX_TRIS skip the hierarchy and brute-force all
    # triangles in one feature matmul. TPU_PBRT_BVH=packet|wide|binary
    # selects the other walkers for A/B comparison. tri_verts is padded
    # (degenerate rows) so fixed-size leaf slices stay in bounds;
    # interaction gathers never index the padding (prim < n_tris).
    import os as _os

    from tpu_pbrt.accel.wide import build_wide, pad_tri_verts

    with TRACE.span("scene/upload") as upload:  # host tables -> device arrays
        sss_rows = mtab.pop("_sss_rows", None)
        dev_bssrdf = None
        if sss_rows:
            # bake each subsurface material's per-channel beam-diffusion
            # profile (core/bssrdf.py module doc: albedo is constant per
            # material, so the (rho, r) spline table of bssrdf.cpp
            # collapses to one radial profile per (material, channel))
            from tpu_pbrt.core.bssrdf import N_RADII, BakedBSSRDF, bake_profile

            M = len(sss_rows)
            b_radii = np.zeros((M, 3, N_RADII), np.float32)
            b_prof = np.zeros((M, 3, N_RADII), np.float32)
            b_cdf = np.zeros((M, 3, N_RADII), np.float32)
            b_rho = np.zeros((M, 3), np.float32)
            b_rmax = np.zeros((M, 3), np.float32)
            b_eta = np.zeros((M,), np.float32)
            for mrow, (sigma_s, sigma_a, g_v, eta_v) in enumerate(sss_rows):
                b_eta[mrow] = eta_v
                for c in range(3):
                    ra, pr, cd, re, rm = bake_profile(
                        float(np.asarray(sigma_s).reshape(-1)[c]),
                        float(np.asarray(sigma_a).reshape(-1)[c]),
                        g_v, eta_v,
                    )
                    b_radii[mrow, c], b_prof[mrow, c], b_cdf[mrow, c] = ra, pr, cd
                    b_rho[mrow, c], b_rmax[mrow, c] = re, rm
            dev_bssrdf = BakedBSSRDF(
                radii=jnp.asarray(b_radii), profile=jnp.asarray(b_prof),
                cdf=jnp.asarray(b_cdf), rho_eff=jnp.asarray(b_rho),
                r_max=jnp.asarray(b_rmax), eta=jnp.asarray(b_eta),
            )

        dev = {
            "tri_verts": jnp.asarray(pad_tri_verts(verts), jnp.float32),
            **({"tri_verts1": jnp.asarray(pad_tri_verts(verts1), jnp.float32)}
               if verts1 is not None else {}),
            "tri_normals": jnp.asarray(normals, jnp.float32),
            "tri_uvs": jnp.asarray(uvs, jnp.float32),
            "tri_mat": jnp.asarray(mat_ids, jnp.int32),
            "tri_light": jnp.asarray(light_ids, jnp.int32),
            "mat": {
                k: (v[0] if k == "_fourier" else jnp.asarray(v))
                for k, v in mtab.items()
            },
            "light": {k: jnp.asarray(v) for k, v in lt.items()},
            "tri_med_in": jnp.asarray(med_in, jnp.int32),
            "tri_med_out": jnp.asarray(med_out, jnp.int32),
            "media": medium_table,
            "world_center": jnp.asarray(wcenter, jnp.float32),
            "world_radius": jnp.float32(wradius),
            "n_lights": jnp.int32(n_lights),
            **({"bssrdf": dev_bssrdf} if dev_bssrdf is not None else {}),
        }
        # Consolidated (T, 16) per-triangle shading row [n0 n1 n2 (9) |
        # uv0 uv1 uv2 (6) | mat*packed_id_base + light+1 as exact f32]: one
        # row-friendly gather replaces four awkward-layout gathers in
        # make_interaction (profiled ~15 vs ~2.6 ns per fetched element on
        # the v5e). Only built when the ids fit the exact-f32 packing.
        n_mats_tab = len(mtab["type"]) if mtab else 0
        id_base = packed_id_base(len(lt["type"]))
        if n_mats_tab * id_base <= 1 << 24:
            pack = (
                np.asarray(mat_ids, np.int64) * id_base
                + np.asarray(light_ids, np.int64)
                + 1
            ).astype(np.float32)[:, None]
            # stored LANE-MAJOR (16, T): axis-1 takes gather at ~2.6 ns per
            # fetched element on the v5e where row-major (T, 16) row gathers
            # cost ~33
            dev["tri_sh16"] = jnp.asarray(
                np.concatenate(
                    [
                        np.asarray(normals, np.float32).reshape(len(normals), 9),
                        np.asarray(uvs, np.float32).reshape(len(uvs), 6),
                        pack,
                    ],
                    axis=1,
                ).T.copy()
            )
        # an anisotropic microfacet lobe points along the shading frame's
        # axes, which pbrt takes from dpdu (triangle.cpp: the default uvs
        # (0,0), (1,0), (1,1) where a mesh has none), as hair does
        mt = np.asarray(mtab["type"])
        aniso = bool(np.any(
            np.isin(mt, (MAT_METAL, MAT_SUBSTRATE, MAT_UBER, MAT_GLASS))
            & (np.asarray(mtab["rough_u"]) != np.asarray(mtab["rough_v"]))
        ))
        if "h_beta_m" in mtab or aniso or tex_atlas is not None:
            # uv-parameterization derivatives per triangle (triangle.cpp
            # dpdu/dpdv): hair and anisotropic lobes need the normalized
            # dpdu as the shading tangent; textured scenes need BOTH raw
            # vectors for ray-differential footprints (interaction.cpp
            # ComputeDifferentials). Stored lane-major; built only when
            # something consumes them.
            duv02 = uvs[:, 0] - uvs[:, 2]
            duv12 = uvs[:, 1] - uvs[:, 2]
            dp02 = verts[:, 0] - verts[:, 2]
            dp12 = verts[:, 1] - verts[:, 2]
            det = duv02[:, 0] * duv12[:, 1] - duv02[:, 1] * duv12[:, 0]
            safe = np.abs(det) > 1e-12
            inv = 1.0 / np.where(safe, det, 1.0)
            dpdu_raw = (duv12[:, 1:2] * dp02 - duv02[:, 1:2] * dp12) * inv[:, None]
            dpdv_raw = (-duv12[:, 0:1] * dp02 + duv02[:, 0:1] * dp12) * inv[:, None]
            dpdu_raw = np.where(safe[:, None], dpdu_raw, 0.0)
            dpdv_raw = np.where(safe[:, None], dpdv_raw, 0.0)
            ln = np.linalg.norm(dpdu_raw, axis=-1, keepdims=True)
            dpdu_n = np.where(ln > 1e-12, dpdu_raw / np.maximum(ln, 1e-20), 0.0)
            if "h_beta_m" in mtab or aniso:
                dev["tri_tanT"] = jnp.asarray(dpdu_n.T.copy(), jnp.float32)
            if tex_atlas is not None:
                dev["tri_difT"] = jnp.asarray(
                    np.concatenate(
                        [dpdu_raw.T, dpdv_raw.T, np.zeros((2, len(verts)))],
                        axis=0,
                    ),
                    jnp.float32,
                )  # (8, T): dpdu(3), dpdv(3), pad
        # per-light triangle vertices (area lights; zeros elsewhere) so
        # light sampling never gathers the big tri_verts array by the
        # per-ray picked light id
        lt_tri = lt["tri"].astype(np.int64)
        lv = np.asarray(verts, np.float32)[np.clip(lt_tri, 0, len(verts) - 1)]
        lv[lt_tri < 0] = 0.0
        dev["light"]["tri_v"] = jnp.asarray(lv)
        from tpu_pbrt.core.lights_dev import pack_light_rows
        from tpu_pbrt.core.smalltab import MAX_DENSE_ROWS

        if len(lt_tri) > MAX_DENSE_ROWS:
            # above the dense select's rows a light is ONE packed row, and the
            # spatial table an ARGUMENT of the program, not a constant in it
            dev["light"]["rows"] = jnp.asarray(pack_light_rows(lt, lv))
            if spatial_distr is not None:
                dev["light_pick"] = spatial_distr.tables()
        if verts1 is not None:
            # NEE/MIS light tables are built from the shutter-START
            # keyframe only; intersections lerp by ray time, so an
            # ANIMATED emissive shape gets statically-positioned light
            # sampling (pbrt samples lights at ref.time). Loud until the
            # light vertex table is time-lerped like Hit.tv.
            lv1 = np.asarray(verts1, np.float32)[
                np.clip(lt_tri, 0, len(verts) - 1)
            ]
            moving = (lt_tri >= 0) & (
                np.abs(lv1 - lv).max(axis=(1, 2)) > 1e-7
            )
            if np.any(moving):
                Warning(
                    f"{int(moving.sum())} area light(s) sit on ANIMATED "
                    "shapes: direct-light sampling uses the shutter-start "
                    "keyframe (approximation; MIS pdfs likewise)"
                )
        if tex_atlas is not None:
            dev["tex_atlas"] = jnp.asarray(tex_atlas, jnp.float32)
        if light_atlas_chunks:
            dev["light_atlas"] = jnp.asarray(light_atlas, jnp.float32)
        from tpu_pbrt.config import cfg

        accel_kind = cfg.bvh
        if verts1 is not None and accel_kind in ("binary", "wide"):
            Warning(
                "motion blur is only supported on the stream/brute accel "
                f"paths; this {accel_kind}-walker render is STATIC at "
                "shutter start"
            )
        if accel_kind == "binary":
            dev["bvh"] = bvh_as_device_dict(bvh)
        elif accel_kind == "wide":
            dev["wbvh"] = build_wide(bvh)
        else:
            from tpu_pbrt.accel.mxu import BRUTE_MAX_TRIS, tri_edge_table
            from tpu_pbrt.accel.treelet import build_treelet_pack

            if len(verts) <= BRUTE_MAX_TRIS:
                with TRACE.span("accel/brute_pack"):
                    dev["brute"] = {"tab": jnp.asarray(tri_edge_table(verts))}
                    if verts1 is not None:  # the shutter-close table
                        dev["brute"]["tab1"] = jnp.asarray(tri_edge_table(verts1))
            elif accel_kind == "packet":
                if verts1 is not None:
                    Warning(
                        "motion blur is only supported on the stream/brute "
                        "accel paths; this packet-walker render is STATIC at "
                        "shutter start"
                    )
                with TRACE.span("accel/treelet_pack"):
                    dev["tpack"] = build_treelet_pack(verts, bvh)
            else:
                from tpu_pbrt.accel.stream import (
                    FUSED_WAVE_RAYS,
                    STREAM_LEAF_TRIS,
                    branch_facts,
                )

                leaf_tris = int(
                    cfg.leaf_tris if cfg.leaf_tris is not None
                    else STREAM_LEAF_TRIS
                )
                with TRACE.span("accel/treelet_pack") as packed:
                    dev["tstream"] = build_treelet_pack(
                        verts, bvh, leaf_tris=leaf_tris, tri_verts1=verts1
                    )
                    packed.args.update(
                        branch_facts(dev["tstream"], FUSED_WAVE_RAYS),
                        stream_wave_rays=FUSED_WAVE_RAYS,
                    )
                # lane-major (9, T) vertex table for _finalize_hits' winner
                # refetch, baked ONCE here: recomputing reshape(T, 9).T
                # inside the wave relayout-copied the whole triangle table
                # per dispatch (cost-pass finding
                # JC-RELAYOUT:stream_intersect:"transpose of (T, 9) buffer")
                T9 = dev["tri_verts"].shape[0]
                dev["tri_verts9T"] = dev["tri_verts"].reshape(T9, 9).T
                if verts1 is not None:
                    dev["tri_verts1_9T"] = dev["tri_verts1"].reshape(T9, 9).T
        if has_envmap:
            dev["envmap"] = jnp.asarray(envmap, jnp.float32)
            dev["env_distr"] = env_distr
            dev["env_w2l"] = jnp.asarray(env_w2l[:3, :3], jnp.float32)
        upload.args["scene_resident_bytes"] = resident_bytes(dev)

    return CompiledScene(
        dev=dev,
        film=film,
        camera=camera,
        sampler=sampler,
        integrator_name=ro.integrator_name,
        integrator_params=ro.integrator_params,
        n_tris=len(verts),
        n_lights=n_lights,
        world_min=wmin,
        world_max=wmax,
        world_center=wcenter,
        world_radius=wradius,
        has_envmap=has_envmap,
        env_distribution=env_distr,
        light_distribution_name=strategy_asked,
        light_strategy_built=strategy_built,
        light_distr=light_distr,
        media=dict(ro.named_media),
        camera_medium_id=camera_medium_id,
        has_null_materials=bool(np.any(np.asarray(mtab["type"])[np.asarray(mat_ids)] == MAT_NONE)),
        has_delta_materials=_has_delta(mtab),
        tex_eval=tex_eval,
        tex_used=frozenset(tex_used),
        spatial_distr=spatial_distr,
    )
