"""killeroo-manylight: `killeroo-class`'s displaced sphere in a room lit by
fixtures, each fixture an emissive mesh.

What an exporter writes for an interior: no `LightSource` line, every lamp a
small closed mesh under `AreaLightSource "diffuse"`, which pbrt (and this
program) turns into ONE area light a triangle. Here 256 fixtures of 32
triangles, an octahedron subdivided once and pushed out to a sphere, wound
outwards: 8,192 light rows. 210 hang on a jittered grid inside the room at
three heights, 46 in rows BEHIND the three partitions (left, right, back;
the front is open to the camera, the top to the sky), so that a fixture is
hidden from part of the room and what a voxel should pick differs from what
the scene's power says. No fixture comes nearer than 0.2 to a surface: the
plain reference samples emitters by area alone, and a wall a few
hundredths from a lamp would be its noise, not the program's. Radiance: one of three
colour temperatures a fixture, its scale log-uniform over `spread` to one,
so that the brightest fixture is seldom the nearest.

The body is `displaced_sphere.py`'s own mesh (the same `n_theta`, `n_phi`,
`mesh_seed` give the same triangles as `killeroo-class`: the function is
loaded from that file, not copied), so the cell's control is exact.

Everything that decides a shape or a place is FIXED by `scene_params`:
meshes, fixtures (places, sizes, temperatures, scales: drawn from
`fixtures.seed`), partitions, camera. `--seed` draws what the device reads
as values: the four reflectances by +-`seeded.kd` a channel and each
fixture's radiance by +-`seeded.radiance_rel` (one factor a fixture).
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location("bench_scenes_" + name, os.path.join(_HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_sphere = _sibling("displaced_sphere")

#: an octahedron, wound outwards
_OCTA_V = np.asarray([[1, 0, 0], [-1, 0, 0], [0, 1, 0], [0, -1, 0], [0, 0, 1], [0, 0, -1]], np.float64)
_OCTA_F = np.asarray(
    [[0, 2, 4], [2, 1, 4], [1, 3, 4], [3, 0, 4], [2, 0, 5], [1, 2, 5], [3, 1, 5], [0, 3, 5]], np.int32
)


def fixture_mesh(subdiv: int):
    """A closed, outward-wound unit sphere of 8 * 4^subdiv triangles ->
    (V (n,3) f64, F (m,3) i32); shared edges share their midpoint."""
    V, F = [tuple(v) for v in _OCTA_V], _OCTA_F.tolist()
    for _ in range(subdiv):
        mid, out = {}, []

        def midpoint(a, b):
            key = (min(a, b), max(a, b))
            if key not in mid:
                m = np.asarray(V[a]) + np.asarray(V[b])
                V.append(tuple(m / np.linalg.norm(m)))
                mid[key] = len(V) - 1
            return mid[key]

        for a, b, c in F:
            ab, bc, ca = midpoint(a, b), midpoint(b, c), midpoint(c, a)
            out += [[a, ab, ca], [ab, b, bc], [ca, bc, c], [ab, bc, ca]]
        F = out
    return np.asarray(V, np.float64), np.asarray(F, np.int32)


def _strip(n: int, lo: float, hi: float, rng):
    """n jittered places along [lo, hi], one a cell."""
    return lo + (np.arange(n) + 0.5 + rng.uniform(-0.25, 0.25, n)) * (hi - lo) / n


def fixtures(p: dict):
    """The fixtures' fixed facts from `scene_params.fixtures` -> (centres
    (n,3), radii (n,), radiance (n,3)). `grid` cells inside the room hold
    one fixture each, jittered inside its cell; `side` more hang in a row
    behind each side partition and `back` behind the back one. Fixture k
    hangs at `heights[k % 3]`; inside the room, one whose place lies within
    `clear` of the body's axis takes an upper height."""
    f = p["fixtures"]
    nx, nz = (int(v) for v in f["grid"])
    rng = np.random.default_rng(int(f["seed"]))
    (x0, x1), (z0, z1) = f["x"], f["z"]
    gx, gz = _strip(nx, x0, x1, rng), _strip(nz, z0, z1, rng)
    x, z = (a.reshape(-1) for a in np.meshgrid(gx, gz, indexing="ij"))
    x = x + rng.uniform(-0.04, 0.04, x.shape)
    z = z + rng.uniform(-0.04, 0.04, z.shape)
    level = np.arange(nx * nz) % 3
    level = np.where((np.hypot(x, z) < float(f["clear"])) & (level == 0), 1 + np.arange(nx * nz) % 2, level)
    ns, nb = int(f["side"]), int(f["back"])
    behind = float(f["behind"])
    (sz0, sz1), (bx0, bx1) = f["side_z"], f["back_x"]
    x = np.concatenate([x, np.full(ns, -behind), np.full(ns, behind), _strip(nb, bx0, bx1, rng)])
    z = np.concatenate([z, _strip(ns, sz0, sz1, rng), _strip(ns, sz0, sz1, rng), np.full(nb, behind)])
    level = np.concatenate([level, np.arange(2 * ns + nb) % 3])
    n = len(x)
    y = np.asarray(f["heights"], np.float64)[level] + rng.uniform(-0.08, 0.08, n)
    radii = rng.uniform(*f["radius"], n)
    tint = np.asarray(f["temperatures"], np.float64)[rng.integers(0, len(f["temperatures"]), n)]
    scale = float(f["spread"]) ** -rng.uniform(0.0, 1.0, n)
    return np.stack([x, y, z], -1), radii, tint * (float(f["L"]) * scale)[:, None]


def _quad(p):
    return np.asarray(p, np.float32).reshape(4, 3), np.asarray([[0, 1, 2], [0, 2, 3]], np.int32)


def build(config: dict, seed: int) -> dict:
    p = config["scene_params"]
    amp = p["seeded"]
    rng = np.random.default_rng(int(seed))
    kd = {
        name: np.clip(np.asarray(p["kd"][name]) + rng.uniform(-1, 1, 3) * float(amp["kd"]), 0.03, 0.95)
        for name in ("floor", "body", "partition", "fixture")
    }
    V, F, N = _sphere._mesh(int(p["n_theta"]), int(p["n_phi"]), int(p["mesh_seed"]))
    room = p["room"]
    (fx0, fx1), (fz0, fz1), y0 = room["floor_x"], room["floor_z"], float(room["floor_y"])
    wx, wz0, wz1 = float(room["partition_x"]), float(room["partition_z"][0]), float(room["partition_z"][1])
    side_top, back_top = float(room["side_top"]), float(room["back_top"])
    quads = [
        ("floor", "floor", [fx0, y0, fz0, fx0, y0, fz1, fx1, y0, fz1, fx1, y0, fz0]),
        # each partition faces the room
        ("left", "partition", [-wx, y0, wz0, -wx, side_top, wz0, -wx, side_top, wz1, -wx, y0, wz1]),
        ("right", "partition", [wx, y0, wz0, wx, y0, wz1, wx, side_top, wz1, wx, side_top, wz0]),
        ("back", "partition", [-wx, y0, wz1, -wx, back_top, wz1, wx, back_top, wz1, wx, y0, wz1]),
    ]
    meshes = []
    for name, colour, corners in quads:
        P, idx = _quad(corners)
        meshes.append({"name": name, "P": P, "indices": idx, "N": None, "Kd": kd[colour], "L": None, "ply": False})
    meshes.append({"name": "body", "P": V, "indices": F, "N": N, "Kd": kd["body"], "L": None, "ply": True})
    centres, radii, radiance = fixtures(p)
    radiance = radiance * (1.0 + rng.uniform(-1, 1, len(centres)) * float(amp["radiance_rel"]))[:, None]
    fv, ff = fixture_mesh(int(p["fixtures"]["subdiv"]))
    for k, (c, r, L) in enumerate(zip(centres, radii, radiance)):
        meshes.append({"name": f"fixture{k}", "P": (c + r * fv).astype(np.float32), "indices": ff, "N": None,
                       "Kd": kd["fixture"], "L": L, "ply": False})
    return {
        "camera": dict(p["camera"]),
        "film": {"xres": int(config["xresolution"]), "yres": int(config["yresolution"])},
        "spp": int(config["pixelsamples"]),
        "maxdepth": int(config["maxdepth"]),
        "sampler": config["sampler"],
        "integrator": config["integrator"],
        "point_lights": [],
        "meshes": meshes,
    }
