"""Built-in test/benchmark scenes.

Stand-ins for the pbrt-v3-scenes distribution (killeroo-simple, cornell
box, ...; SURVEY.md 'Workload configs'), which is not shipped in this
environment: a classic Cornell box in .pbrt text form, and a procedural
killeroo-class mesh (comparable triangle count and shading mix) built
through the pbrt API so the benchmark exercises the same code path as real
scene files — parser -> API state machine -> scene compiler -> wavefront.
"""

from __future__ import annotations

import numpy as np

from tpu_pbrt.scene.api import Options, PbrtAPI, parse_string, pbrt_init
from tpu_pbrt.scene.paramset import ParamSet


def cornell_box_text(res=256, spp=16, integrator="directlighting", maxdepth=5, filename="", sampler="zerotwosequence"):
    """The cornell-box config (SURVEY.md: DirectLightingIntegrator, area
    light + Lambertian). Classic Cornell geometry, meters scaled to [0,1]."""
    return f'''
Integrator "{integrator}" "integer maxdepth" [{maxdepth}]
Sampler "{sampler}" "integer pixelsamples" [{spp}]
PixelFilter "box"
Film "image" "integer xresolution" [{res}] "integer yresolution" [{res}] "string filename" ["{filename}"]
LookAt 0.5 0.5 -1.4  0.5 0.5 0  0 1 0
Camera "perspective" "float fov" [40]
WorldBegin
# floor (normal +y)
Material "matte" "rgb Kd" [0.73 0.73 0.73]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [0 0 0  0 0 1  1 0 1  1 0 0]
# ceiling (normal -y)
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [0 1 0  1 1 0  1 1 1  0 1 1]
# back wall (normal -z)
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [0 0 1  0 1 1  1 1 1  1 0 1]
# left wall, red (normal +x)
Material "matte" "rgb Kd" [0.65 0.05 0.05]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [0 0 0  0 1 0  0 1 1  0 0 1]
# right wall, green (normal -x)
Material "matte" "rgb Kd" [0.12 0.45 0.15]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [1 0 0  1 0 1  1 1 1  1 1 0]
# short block
Material "matte" "rgb Kd" [0.73 0.73 0.73]
AttributeBegin
Translate 0.65 0.15 0.3
Rotate -18 0 1 0
Scale 0.15 0.15 0.15
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3  4 6 5 4 7 6  0 4 1 1 4 5  2 6 3 3 6 7  1 5 2 2 5 6  0 3 7 0 7 4]
  "point P" [-1 -1 -1  1 -1 -1  1 -1 1  -1 -1 1  -1 1 -1  1 1 -1  1 1 1  -1 1 1]
AttributeEnd
# tall block
AttributeBegin
Translate 0.3 0.3 0.65
Rotate 15 0 1 0
Scale 0.15 0.3 0.15
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3  4 6 5 4 7 6  0 4 1 1 4 5  2 6 3 3 6 7  1 5 2 2 5 6  0 3 7 0 7 4]
  "point P" [-1 -1 -1  1 -1 -1  1 -1 1  -1 -1 1  -1 1 -1  1 1 -1  1 1 1  -1 1 1]
AttributeEnd
# light (faces -y, just below ceiling)
AttributeBegin
AreaLightSource "diffuse" "rgb L" [15 11 5]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [0.35 0.998 0.35  0.65 0.998 0.35  0.65 0.998 0.65  0.35 0.998 0.65]
AttributeEnd
WorldEnd
'''


def compile_api(api: PbrtAPI):
    """Compile the world accumulated so far (WorldEnd's compile step without
    the render or the state reset) -> (CompiledScene, integrator)."""
    from tpu_pbrt.integrators import make_integrator
    from tpu_pbrt.scene.compiler import compile_scene

    scene = compile_scene(api)
    integ = make_integrator(
        api.render_options.integrator_name, api.render_options.integrator_params, scene, api.options
    )
    return scene, integ


def _crown_envmap_path():
    """Procedural HDR sky (gradient + sun disk) under refimg/ — the
    crown-class bench's environment light. Written on every call (64x128,
    deterministic, replaced atomically): `*.pfm` is git-ignored, so a
    file found there may be older than this code."""
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "refimg", "crown_env.pfm")
    h, w = 64, 128
    th = np.linspace(0, np.pi, h)[:, None]
    ph = np.linspace(0, 2 * np.pi, w)[None, :]
    sky = np.stack(
        [
            0.35 + 0.25 * np.cos(th) * np.ones_like(ph),
            0.45 + 0.30 * np.cos(th) * np.ones_like(ph),
            0.75 + 0.25 * np.cos(th) * np.ones_like(ph),
        ],
        axis=-1,
    ).astype(np.float32)
    # warm sun disk
    sun_dir = (0.45 * np.pi, 0.3 * np.pi)
    d2 = (th - sun_dir[0]) ** 2 + (ph - sun_dir[1]) ** 2
    sun = np.exp(-d2 / 0.004)[..., None] * np.asarray([60.0, 50.0, 35.0])
    img = (sky + sun).astype(np.float32)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    from tpu_pbrt.utils.imageio import write_image

    tmp = f"{path}.{os.getpid()}.pfm"
    write_image(tmp, img)
    os.replace(tmp, path)
    return path


def make_crown_like(res=512, spp=64, maxdepth=5, options=None,
                    n_theta=500, n_phi=1000) -> PbrtAPI:
    """crown-class stand-in (BASELINE.md crown rows): >=1M-triangle
    displaced mesh in GLASS, two metal-GGX side pieces, matte ground,
    HDR environment light with 2D-CDF importance sampling — the
    feature set of pbrt-v3-scenes/crown at a procedural geometry
    budget (the PLYs are unavailable in this environment)."""
    api = pbrt_init(options or Options(quiet=True))
    env = _crown_envmap_path()
    parse_string(
        f"""
Integrator "path" "integer maxdepth" [{maxdepth}]
Sampler "zerotwosequence" "integer pixelsamples" [{spp}]
PixelFilter "box"
Film "image" "integer xresolution" [{res}] "integer yresolution" [{res}] "string filename" [""]
LookAt 0 1.4 -3.6  0 0.4 0  0 1 0
Camera "perspective" "float fov" [39]
WorldBegin
LightSource "infinite" "string mapname" ["{env}"]
Material "matte" "rgb Kd" [0.45 0.42 0.38]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-8 -0.75 -8  -8 -0.75 8  8 -0.75 8  8 -0.75 -8]
Material "glass" "float eta" [1.5] "rgb Kr" [1 1 1] "rgb Kt" [1 1 1]
""",
        api,
        render=False,
    )
    V, F, N = _displaced_sphere(n_theta, n_phi)
    ps = ParamSet()
    ps.add("integer indices", F.reshape(-1).tolist())
    ps.add("point P", V.reshape(-1).tolist())
    ps.add("normal N", N.reshape(-1).tolist())
    api.shape("trianglemesh", ps)
    # two metal-GGX side pieces (rough + brushed)
    parse_string(
        """
AttributeBegin
Material "metal" "float roughness" [0.05]
Translate -1.7 -0.15 0.4
Scale 0.55 0.55 0.55
""",
        api,
        render=False,
    )
    V2, F2, N2 = _displaced_sphere(140, 280, seed=11)
    ps2 = ParamSet()
    ps2.add("integer indices", F2.reshape(-1).tolist())
    ps2.add("point P", V2.reshape(-1).tolist())
    ps2.add("normal N", N2.reshape(-1).tolist())
    api.shape("trianglemesh", ps2)
    parse_string(
        """
AttributeEnd
AttributeBegin
Material "metal" "float roughness" [0.18] "float uroughness" [0.3] "float vroughness" [0.05]
Translate 1.7 -0.1 0.6
Scale 0.6 0.6 0.6
""",
        api,
        render=False,
    )
    V3, F3, N3 = _displaced_sphere(140, 280, seed=23)
    ps3 = ParamSet()
    ps3.add("integer indices", F3.reshape(-1).tolist())
    ps3.add("point P", V3.reshape(-1).tolist())
    ps3.add("normal N", N3.reshape(-1).tolist())
    api.shape("trianglemesh", ps3)
    parse_string("AttributeEnd\n", api, render=False)
    return api


def make_cornell(res=256, spp=16, integrator="directlighting", maxdepth=5, options=None, sampler="zerotwosequence") -> PbrtAPI:
    """Parse the Cornell box up to (not including) WorldEnd, so the caller
    controls compilation/rendering via compile_api()."""
    api = pbrt_init(options or Options(quiet=True))
    text = cornell_box_text(res, spp, integrator, maxdepth, sampler=sampler)
    text = text.rsplit("WorldEnd", 1)[0]
    parse_string(text, api, render=False)
    return api


def _displaced_sphere(n_theta=180, n_phi=360, seed=7):
    """Procedural blobby mesh, ~(n_theta-1)*n_phi*2 triangles, with shading
    normals — a killeroo-class triangle count with curvature everywhere."""
    rng = np.random.default_rng(seed)
    amps = rng.uniform(0.02, 0.08, size=6)
    freqs = rng.integers(2, 9, size=(6, 2))
    th = np.linspace(1e-3, np.pi - 1e-3, n_theta)
    ph = np.linspace(0.0, 2 * np.pi, n_phi, endpoint=False)
    T, P = np.meshgrid(th, ph, indexing="ij")
    r = np.ones_like(T)
    for a, (f1, f2) in zip(amps, freqs):
        r = r + a * np.sin(f1 * T) * np.cos(f2 * P)
    x = r * np.sin(T) * np.cos(P)
    y = r * np.cos(T)
    z = r * np.sin(T) * np.sin(P)
    V = np.stack([x, y, z], axis=-1).reshape(-1, 3)

    def vid(i, j):
        return i * n_phi + (j % n_phi)

    idx = []
    for i in range(n_theta - 1):
        for j in range(n_phi):
            idx.append((vid(i, j), vid(i + 1, j), vid(i + 1, j + 1)))
            idx.append((vid(i, j), vid(i + 1, j + 1), vid(i, j + 1)))
    F = np.asarray(idx, np.int64)
    # smooth vertex normals
    fn = np.cross(V[F[:, 1]] - V[F[:, 0]], V[F[:, 2]] - V[F[:, 0]])
    N = np.zeros_like(V)
    for k in range(3):
        np.add.at(N, F[:, k], fn)
    N /= np.maximum(np.linalg.norm(N, axis=-1, keepdims=True), 1e-20)
    return V, F, N


def _killeroo_like_head(res, spp, integrator, maxdepth) -> str:
    """Everything of the killeroo-like scene up to the big mesh's Shape
    directive, shared by the in-memory builder and the file writer."""
    return f'''
Integrator "{integrator}" "integer maxdepth" [{maxdepth}]
Sampler "zerotwosequence" "integer pixelsamples" [{spp}]
PixelFilter "box"
Film "image" "integer xresolution" [{res}] "integer yresolution" [{res}] "string filename" [""]
LookAt 0 1.2 -3.4  0 0.3 0  0 1 0
Camera "perspective" "float fov" [38]
WorldBegin
AttributeBegin
AreaLightSource "diffuse" "rgb L" [18 17 15]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-1 2.98 -1  1 2.98 -1  1 2.98 1  -1 2.98 1]
AttributeEnd
LightSource "point" "rgb I" [4 4 5] "point from" [2.5 2 -2.5]
Material "matte" "rgb Kd" [0.82 0.78 0.75]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-6 -0.72 -6  -6 -0.72 6  6 -0.72 6  6 -0.72 -6]
Material "matte" "rgb Kd" [0.35 0.30 0.25]
'''


def write_killeroo_like(path, res=512, spp=64, integrator="path",
                        maxdepth=5, n_theta=180, n_phi=360, ply=None) -> str:
    """The scene of make_killeroo_like as a `.pbrt` file on disk, its
    mesh in a binary PLY in the same directory (`ply`, by default
    `<stem>.ply`; several resolutions of the scene can name one file) —
    what `tpu_pbrt.main` and the serve daemon take from a user.
    Deterministic: the same arguments write the same bytes. Returns
    `path`."""
    import os

    from tpu_pbrt.scene.plyreader import write_ply

    V, F, N = _displaced_sphere(n_theta, n_phi)
    ply = ply or os.path.splitext(path)[0] + ".ply"
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    write_ply(ply, V, F, normals=N)
    with open(path, "w") as fh:
        fh.write(_killeroo_like_head(res, spp, integrator, maxdepth))
        fh.write(
            f'Shape "plymesh" "string filename" ["{os.path.basename(ply)}"]\n'
            "WorldEnd\n"
        )
    return path


def make_killeroo_like(res=512, spp=64, integrator="path", maxdepth=5,
                       n_theta=180, n_phi=360, options=None) -> PbrtAPI:
    """killeroo-simple stand-in: one ~128k-triangle matte mesh over a ground
    plane, one area light + point fill, path integrator (the [D]
    killeroo-simple config: PathIntegrator, matte BSDF, trimesh)."""
    api = pbrt_init(options or Options(quiet=True))
    parse_string(
        _killeroo_like_head(res, spp, integrator, maxdepth), api,
        render=False,
    )
    V, F, N = _displaced_sphere(n_theta, n_phi)
    ps = ParamSet()
    ps.add("integer indices", F.reshape(-1).tolist())
    ps.add("point P", V.reshape(-1).tolist())
    ps.add("normal N", N.reshape(-1).tolist())
    api.shape("trianglemesh", ps)
    # WorldEnd handled by caller via api.world_end(render=...)
    return api
