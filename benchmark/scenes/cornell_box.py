"""cornell-path: this repo's `scenes/cornell-path.pbrt` as a scene description.

The Cornell box of the file: floor, ceiling, back wall, a red and a green
side wall, a short and a tall block, one light quad 2 mm under the ceiling;
36 triangles, matte, one diffuse area light. `scene_params` of the
configuration holds the file's own values (camera, reflectances, radiance,
each block's Translate / Rotate / Scale); with every amplitude under
`scene_params.seeded` at 0 the description IS the file
(tests/test_cornell_config.py compiles both and compares the triangles).

`scenedesc.py` writes world-space meshes, so the blocks' transforms are
applied here, as pbrt composes them: p_world = Translate(Rotate(Scale(p))),
`Rotate a 0 1 0` turning +z towards +x.

`--seed` draws what the device reads as VALUES, each about the file's own:
the two blocks' rotation angles, the three reflectances, the light's
radiance. Counts, the camera, the film and the box itself are fixed: they
decide shapes or constants of the compiled program
(`benchmark/seed_check.py cornell-path`).
"""

from __future__ import annotations

import numpy as np

QUAD = np.asarray([[0, 1, 2], [0, 2, 3]], np.int32)
#: the file's block: a cube of half-width 1, faces wound as the file winds them
CUBE_P = np.asarray(
    [[-1, -1, -1], [1, -1, -1], [1, -1, 1], [-1, -1, 1], [-1, 1, -1], [1, 1, -1], [1, 1, 1], [-1, 1, 1]],
    np.float64,
)
CUBE_F = np.asarray(
    [0, 1, 2, 0, 2, 3, 4, 6, 5, 4, 7, 6, 0, 4, 1, 1, 4, 5, 2, 6, 3, 3, 6, 7, 1, 5, 2, 2, 5, 6, 0, 3, 7, 0, 7, 4],
    np.int32,
).reshape(-1, 3)
#: the box, as the file lists it: name, reflectance key, four corners
WALLS = (
    ("floor", "white", [0, 0, 0, 0, 0, 1, 1, 0, 1, 1, 0, 0]),
    ("ceiling", "white", [0, 1, 0, 1, 1, 0, 1, 1, 1, 0, 1, 1]),
    ("back", "white", [0, 0, 1, 0, 1, 1, 1, 1, 1, 1, 0, 1]),
    ("left", "red", [0, 0, 0, 0, 1, 0, 0, 1, 1, 0, 0, 1]),
    ("right", "green", [1, 0, 0, 1, 0, 1, 1, 1, 1, 1, 1, 0]),
)
LIGHT_P = [0.35, 0.998, 0.35, 0.65, 0.998, 0.35, 0.65, 0.998, 0.65, 0.35, 0.998, 0.65]


def _block(translate, rotate_y_deg: float, scale) -> np.ndarray:
    a = np.radians(float(rotate_y_deg))
    rot = np.asarray([[np.cos(a), 0, np.sin(a)], [0, 1, 0], [-np.sin(a), 0, np.cos(a)]])
    return ((CUBE_P * np.asarray(scale, np.float64)) @ rot.T + np.asarray(translate, np.float64)).astype(np.float32)


def build(config: dict, seed: int) -> dict:
    p = config["scene_params"]
    amp = p["seeded"]
    rng = np.random.default_rng(int(seed))
    kd = {
        name: np.clip(np.asarray(p["kd"][name]) + rng.uniform(-1, 1, 3) * float(amp["kd"]), 0.03, 0.95)
        for name in ("white", "red", "green")
    }
    turns = {name: rng.uniform(-1, 1) * float(amp["block_rotation_deg"]) for name in ("short", "tall")}
    radiance = np.asarray(p["area_L"]) * (1.0 + rng.uniform(-1, 1, 3) * float(amp["radiance_rel"]))

    def mesh(name, P, F, kd_name, L=None):
        return {"name": name, "P": np.asarray(P, np.float32).reshape(-1, 3), "indices": F, "N": None,
                "Kd": kd[kd_name], "L": L, "ply": False}

    meshes = [mesh(name, corners, QUAD, colour) for name, colour, corners in WALLS]
    for name in ("short", "tall"):
        b = p["blocks"][name]
        meshes.append(mesh(name, _block(b["translate"], b["rotate_y_deg"] + turns[name], b["scale"]), CUBE_F, "white"))
    # the light's own surface keeps the material in force in the file: white
    meshes.append(mesh("light", LIGHT_P, QUAD, "white", L=radiance))
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
