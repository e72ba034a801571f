"""crown-materials: crown-geometry's meshes in the crown's materials, gold
and silver microfacet metal and glass gems, under an HDR environment map.

The meshes are crown-geometry's, built by its own `geometry` (imported, not
copied): the displaced body and the 38 band spheres set into it, at the
configuration's sizes, and the ground quad. crown-geometry's light quad and
point light are left out: the published light is the environment.

Materials (`scene_params.materials`): the body gold metal, band 2 silver
anisotropic metal, bands 1 and 3 smooth glass, the ground matte. The light
is one `LightSource "infinite"` with a lat-long map (`env_map`) that this
writer builds: a sky gradient, a sun lobe and two rectangular studio
panels, in pbrt's light space (z up; `env.rotate` turns it to the scene's
y up).

Everything that decides a shape or a compiled constant is FIXED by the
configuration: meshes, camera, the map's size and the places of the sun and
the panels. `--seed` draws values the device reads and nothing else: the
ground's reflectance by at most `seeded.kd` a channel, and the map's
overall scale by at most `seeded.env_scale` of itself.
"""

from __future__ import annotations

import importlib.util
import math
import os

import numpy as np


def _crown_geometry():
    """crown-geometry's scene writer, from the file beside this one."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "crown_geometry.py")
    spec = importlib.util.spec_from_file_location("bench_scenes_crown_geometry_for_materials", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _unit(v):
    v = np.asarray(v, np.float64)
    return v / np.linalg.norm(v)


def env_map(env: dict) -> np.ndarray:
    """The (height, width, 3) float32 lat-long map, row 0 at theta = 0
    (light-space +z), column x at phi = 2 pi (x + 0.5) / width, before the
    seed's scale. Radiance at each texel's centre direction: a sky gradient
    above the horizon (theta < pi/2) and a dark floor below it, the sun lobe
    exp(-angle^2 / sun_sigma2) * sun_peak about `sun_dir`, and each panel's
    radiance over its theta x phi rectangle (degrees)."""
    h, w = int(env["height"]), int(env["width"])
    th = (np.arange(h) + 0.5) / h * math.pi
    ph = (np.arange(w) + 0.5) / w * 2 * math.pi
    T, P = np.meshgrid(th, ph, indexing="ij")
    ct = np.cos(T)[..., None]
    zen, hor, floor = (np.asarray(env[k], np.float64) for k in ("zenith", "horizon", "floor"))
    sky = np.where(ct > 0, hor + (zen - hor) * ct, floor)
    d = np.stack([np.sin(T) * np.cos(P), np.sin(T) * np.sin(P), np.cos(T)], axis=-1)
    sun = _unit(env["sun_dir"])
    ang = np.arccos(np.clip(d @ sun, -1.0, 1.0))
    img = sky + np.exp(-ang * ang / float(env["sun_sigma2"]))[..., None] * np.asarray(env["sun_peak"])
    tdeg, pdeg = np.degrees(T), np.degrees(P)
    for panel in env["panels"]:
        (t0, t1), (p0, p1) = panel["theta_deg"], panel["phi_deg"]
        inside = (tdeg >= t0) & (tdeg < t1) & (pdeg >= p0) & (pdeg < p1)
        img = img + inside[..., None] * np.asarray(panel["L"], np.float64)
    return img.astype(np.float32)


def build(config: dict, seed: int) -> dict:
    p = config["scene_params"]
    rng = np.random.default_rng(int(seed))
    s = float(p["seeded"]["kd"])
    ground_kd = np.clip(np.asarray(p["ground_kd"]) + rng.uniform(-s, s, 3), 0.05, 0.95)
    scale = 1.0 + rng.uniform(-1.0, 1.0) * float(p["seeded"]["env_scale"])
    gq = np.asarray([-6, -0.72, -6, -6, -0.72, 6, 6, -0.72, 6, 6, -0.72, -6], np.float32).reshape(4, 3)
    gf = np.asarray([[0, 1, 2], [0, 2, 3]], np.int32)
    min_ply = int(p["ply_over_triangles"])
    mats = p["materials"]
    meshes = [{"name": "ground", "P": gq, "indices": gf, "N": None, "ply": False,
               "material": {"type": "matte", "Kd": ground_kd.tolist()}}]
    for name, V, F, N, _ in _crown_geometry().geometry(p):
        # crown-geometry names a band's meshes b<band>m<k>
        mat = mats["body"] if name == "body" else mats["bands"][int(name[1:name.index("m")])]
        meshes.append({"name": name, "P": V, "indices": F, "N": N, "ply": len(F) > min_ply,
                       "material": dict(mat)})
    env = p["env"]
    return {
        "camera": dict(p["camera"]),
        "film": {"xres": int(config["xresolution"]), "yres": int(config["yresolution"])},
        "spp": int(config["pixelsamples"]),
        "maxdepth": int(config["maxdepth"]),
        "sampler": config["sampler"],
        "integrator": config["integrator"],
        "meshes": meshes,
        "env": {"map": env_map(env) * np.float32(scale), "rotate": list(env["rotate"])},
    }
