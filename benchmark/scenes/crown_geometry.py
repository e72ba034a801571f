"""crown-geometry stand-in: pbrt-v3-scenes' crown at its triangle count, in
matte, as one displaced body with bands of smaller meshes set into it.

The crown's PLYs are not in this environment. What stands in keeps the two
things about its geometry that the program's scene compiler has to cope
with: the count (about 3.5 million triangles) and the fact that a crown is
MANY meshes that run through each other. A displaced sphere with smooth
shading normals carries most of the triangles; `bands` of smaller displaced
spheres sit with their centres on its surface, half inside it, close enough
along a band that each one's bounds overlap its neighbours' and the body's.
A ground quad, a light quad and a point light as in `killeroo-class`.

Everything that decides a shape or a compiled constant is FIXED by the
configuration (`scene_params`): meshes, camera, lights. `--seed` draws what
the device reads as values and nothing else: each mesh's reflectance, by at
most `seeded.kd` a channel around a base of its own (PERF.md, Findings
PR 27: a wider draw moves the rays of a frame by more than the bound on
`rays_per_s` can bear).
"""

from __future__ import annotations

import numpy as np


def _radius(T, P, seed: int):
    """The displaced unit sphere's radius at polar angle T, azimuth P."""
    rng = np.random.default_rng(seed)
    amps = rng.uniform(0.02, 0.08, size=6)
    freqs = rng.integers(2, 9, size=(6, 2))
    r = np.ones_like(T)
    for a, (f1, f2) in zip(amps, freqs):
        r = r + a * np.sin(f1 * T) * np.cos(f2 * P)
    return r


def _on_sphere(r, T, P):
    return np.stack([r * np.sin(T) * np.cos(P), r * np.cos(T), r * np.sin(T) * np.sin(P)], axis=-1)


def _displaced_sphere(n_theta: int, n_phi: int, seed: int, scale: float = 1.0, centre=(0.0, 0.0, 0.0)):
    """(n_theta-1) * n_phi * 2 triangles with area-weighted vertex normals
    -> (V (n,3) f32, F (m,3) i32, N (n,3) f32)."""
    th = np.linspace(1e-3, np.pi - 1e-3, n_theta)
    ph = np.linspace(0.0, 2 * np.pi, n_phi, endpoint=False)
    T, P = np.meshgrid(th, ph, indexing="ij")
    V = _on_sphere(_radius(T, P, seed), T, P).reshape(-1, 3)
    i, j = np.meshgrid(np.arange(n_theta - 1), np.arange(n_phi), indexing="ij")
    a = (i * n_phi + j).reshape(-1)
    b = ((i + 1) * n_phi + j).reshape(-1)
    c = ((i + 1) * n_phi + (j + 1) % n_phi).reshape(-1)
    d = (i * n_phi + (j + 1) % n_phi).reshape(-1)
    F = np.stack([np.stack([a, b, c], -1), np.stack([a, c, d], -1)], axis=1).reshape(-1, 3)
    fn = np.cross(V[F[:, 1]] - V[F[:, 0]], V[F[:, 2]] - V[F[:, 0]])
    N = np.stack(
        [sum(np.bincount(F[:, k], weights=fn[:, ax], minlength=len(V)) for k in range(3)) for ax in range(3)],
        axis=-1,
    )
    N /= np.maximum(np.linalg.norm(N, axis=-1, keepdims=True), 1e-20)
    V = V * scale + np.asarray(centre, np.float64)
    return V.astype(np.float32), F.astype(np.int32), N.astype(np.float32)


def _quad(p):
    return np.asarray(p, np.float32).reshape(4, 3), np.asarray([[0, 1, 2], [0, 2, 3]], np.int32)


def geometry(p: dict) -> list:
    """The fixed meshes, body first -> [(name, V, F, N, base reflectance)].
    The bands' base reflectances come from `mesh_seed`, not from `--seed`."""
    body = p["body"]
    out = [("body", *_displaced_sphere(int(body["n_theta"]), int(body["n_phi"]), int(p["mesh_seed"])), body["kd"])]
    rng = np.random.default_rng([int(p["mesh_seed"]), 1])
    for b, band in enumerate(p["bands"]):
        n = int(band["count"])
        for k in range(n):
            t = np.radians(float(band["polar_deg"]))
            ph = 2 * np.pi * (k + float(band["phase"])) / n
            centre = _on_sphere(_radius(np.asarray(t), np.asarray(ph), int(p["mesh_seed"])), t, ph)
            V, F, N = _displaced_sphere(
                int(band["n_theta"]), int(band["n_phi"]), int(p["mesh_seed"]) + 1 + len(out),
                scale=float(band["radius"]), centre=centre,
            )
            out.append((f"b{b}m{k}", V, F, N, rng.uniform(0.15, 0.75, 3).round(3).tolist()))
    return out


def build(config: dict, seed: int) -> dict:
    p = config["scene_params"]
    rng = np.random.default_rng(int(seed))
    s = float(p["seeded"]["kd"])
    jit = lambda base: np.clip(np.asarray(base) + rng.uniform(-s, s, 3), 0.05, 0.95)  # noqa: E731
    lq, lf = _quad([-1, 2.98, -1, 1, 2.98, -1, 1, 2.98, 1, -1, 2.98, 1])
    gq, gf = _quad([-6, -0.72, -6, -6, -0.72, 6, 6, -0.72, 6, 6, -0.72, -6])
    min_ply = int(p["ply_over_triangles"])
    meshes = [
        # the light's own surface: pbrt's default material (matte 0.5)
        {"name": "light", "P": lq, "indices": lf, "N": None, "Kd": [0.5, 0.5, 0.5], "L": p["area_L"], "ply": False},
        {"name": "ground", "P": gq, "indices": gf, "N": None, "Kd": jit(p["ground_kd"]), "L": None, "ply": False},
    ]
    for name, V, F, N, kd in geometry(p):
        meshes.append({"name": name, "P": V, "indices": F, "N": N, "Kd": jit(kd), "L": None,
                       "ply": len(F) > min_ply})
    return {
        "camera": dict(p["camera"]),
        "film": {"xres": int(config["xresolution"]), "yres": int(config["yresolution"])},
        "spp": int(config["pixelsamples"]),
        "maxdepth": int(config["maxdepth"]),
        "sampler": config["sampler"],
        "integrator": config["integrator"],
        "point_lights": [{"from": p["point_from"], "I": p["point_I"]}],
        "meshes": meshes,
    }
