"""killeroo-class stand-in: a displaced sphere of killeroo-simple's triangle
count over a ground plane, under an area light and a point light.

pbrt-v3-scenes' killeroo PLYs are not in this environment. The geometry is
copied from the program's own stand-in (`tpu_pbrt/scenes.py::
write_killeroo_like`, PR 21): (n_theta-1) * n_phi * 2 triangles with smooth
shading normals, the same camera, ground, light quad and point light.

The mesh is FIXED (`mesh_seed` of the configuration): the program's SAH
build gives another treelet count for another mesh, which is another
compiled program (PERF.md, Findings PR 24). `--seed` draws only what the
device reads as values: the two reflectances. The lights stay as configured,
powers and positions: the program bakes its spatial light-pick distribution
into the compiled chunk program as a constant (benchmark/seed_check.py saw a
seeded point-light position change the lowered program).
"""

from __future__ import annotations

import numpy as np


def _mesh(n_theta: int, n_phi: int, seed: int):
    rng = np.random.default_rng(seed)
    amps = rng.uniform(0.02, 0.08, size=6)
    freqs = rng.integers(2, 9, size=(6, 2))
    th = np.linspace(1e-3, np.pi - 1e-3, n_theta)
    ph = np.linspace(0.0, 2 * np.pi, n_phi, endpoint=False)
    T, P = np.meshgrid(th, ph, indexing="ij")
    r = np.ones_like(T)
    for a, (f1, f2) in zip(amps, freqs):
        r = r + a * np.sin(f1 * T) * np.cos(f2 * P)
    V = np.stack(
        [r * np.sin(T) * np.cos(P), r * np.cos(T), r * np.sin(T) * np.sin(P)], axis=-1
    ).reshape(-1, 3)
    i, j = np.meshgrid(np.arange(n_theta - 1), np.arange(n_phi), indexing="ij")
    a = (i * n_phi + j).reshape(-1)
    b = ((i + 1) * n_phi + j).reshape(-1)
    c = ((i + 1) * n_phi + (j + 1) % n_phi).reshape(-1)
    d = (i * n_phi + (j + 1) % n_phi).reshape(-1)
    F = np.stack([np.stack([a, b, c], -1), np.stack([a, c, d], -1)], axis=1).reshape(-1, 3)
    fn = np.cross(V[F[:, 1]] - V[F[:, 0]], V[F[:, 2]] - V[F[:, 0]])
    N = np.zeros_like(V)
    for k in range(3):
        np.add.at(N, F[:, k], fn)
    N /= np.maximum(np.linalg.norm(N, axis=-1, keepdims=True), 1e-20)
    return V.astype(np.float32), F.astype(np.int32), N.astype(np.float32)


def _quad(p):
    return np.asarray(p, np.float32).reshape(4, 3), np.asarray([[0, 1, 2], [0, 2, 3]], np.int32)


def build(config: dict, seed: int) -> dict:
    p = config["scene_params"]
    rng = np.random.default_rng(int(seed))
    V, F, N = _mesh(int(p["n_theta"]), int(p["n_phi"]), int(p["mesh_seed"]))
    lq, lf = _quad([-1, 2.98, -1, 1, 2.98, -1, 1, 2.98, 1, -1, 2.98, 1])
    gq, gf = _quad([-6, -0.72, -6, -6, -0.72, 6, 6, -0.72, 6, 6, -0.72, -6])
    jit = lambda base, s: np.clip(np.asarray(base) + rng.uniform(-s, s, 3), 0.05, 0.95)  # noqa: E731
    ground_kd = jit(p["ground_kd"], 0.1)
    mesh_kd = jit(p["mesh_kd"], 0.1)
    return {
        "camera": dict(p["camera"]),
        "film": {"xres": int(config["xresolution"]), "yres": int(config["yresolution"])},
        "spp": int(config["pixelsamples"]),
        "maxdepth": int(config["maxdepth"]),
        "sampler": config["sampler"],
        "integrator": config["integrator"],
        "point_lights": [{"from": p["point_from"], "I": p["point_I"]}],
        "meshes": [
            # the light's own surface: pbrt's default material (matte 0.5)
            {"name": "light", "P": lq, "indices": lf, "N": None, "Kd": [0.5, 0.5, 0.5],
             "L": p["area_L"], "ply": False},
            {"name": "ground", "P": gq, "indices": gf, "N": None, "Kd": ground_kd,
             "L": None, "ply": False},
            {"name": "body", "P": V, "indices": F, "N": N, "Kd": mesh_kd,
             "L": None, "ply": True},
        ],
    }
