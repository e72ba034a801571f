"""A scene as plain data, and the one writer that turns it into what the
program takes from a user: a `.pbrt` file, its large meshes in binary PLY.

A scene description is a dict:

    camera        {"eye", "look", "up": 3 floats each, "fov": degrees}
    film          {"xres", "yres"}
    spp, maxdepth integers
    sampler       the Sampler directive's name
    integrator    the Integrator directive's name
    meshes        list of {"name", "P": (V,3) float32 world space,
                  "indices": (F,3) int32, "N": (V,3) float32 or None,
                  "Kd": 3 floats (matte), "L": 3 floats or None (a
                  one-sided diffuse area light on the side of
                  cross(p1-p0, p2-p0)), "ply": bool}
    point_lights  list of {"from": 3 floats, "I": 3 floats}

The scene writers under `scenes/` build it from a configuration and a seed;
the plain reference (`reference.py`) renders it directly, so it never sees
a byte that the program has parsed, built or packed.
"""

from __future__ import annotations

import os

import numpy as np


def _floats(a) -> str:
    # %.9g round-trips a float32: the program parses the value the
    # reference computes with
    return " ".join("%.9g" % float(v) for v in np.asarray(a, np.float32).reshape(-1))


def write_ply(path: str, P, indices, N=None) -> None:
    """Binary little-endian PLY: x y z [nx ny nz], faces as uchar+3 int."""
    v = np.asarray(P, "<f4")
    f = np.asarray(indices, "<i4")
    props = "property float x\nproperty float y\nproperty float z\n"
    if N is not None:
        props += "property float nx\nproperty float ny\nproperty float nz\n"
        v = np.hstack([v, np.asarray(N, "<f4")])
    head = (
        "ply\nformat binary_little_endian 1.0\n"
        f"element vertex {len(v)}\n{props}"
        f"element face {len(f)}\n"
        "property list uchar int vertex_indices\nend_header\n"
    )
    rec = np.empty((len(f), 13), np.uint8)
    rec[:, 0] = 3
    rec[:, 1:] = f.view(np.uint8).reshape(len(f), 12)
    with open(path, "wb") as fh:
        fh.write(head.encode("ascii"))
        fh.write(v.tobytes())
        fh.write(rec.tobytes())


def write_scene(desc: dict, out_dir: str, stem: str) -> str:
    """Write `<out_dir>/<stem>.pbrt` (and `<stem>-<mesh>.ply` for meshes
    marked `ply`); returns the `.pbrt` path. The same description writes
    the same bytes."""
    os.makedirs(out_dir, exist_ok=True)
    cam, film = desc["camera"], desc["film"]
    out = [
        f'Integrator "{desc["integrator"]}" "integer maxdepth" [{int(desc["maxdepth"])}]',
        f'Sampler "{desc["sampler"]}" "integer pixelsamples" [{int(desc["spp"])}]',
        'PixelFilter "box"',
        f'Film "image" "integer xresolution" [{int(film["xres"])}] '
        f'"integer yresolution" [{int(film["yres"])}] "string filename" [""]',
        f'LookAt {_floats(cam["eye"])}  {_floats(cam["look"])}  {_floats(cam["up"])}',
        f'Camera "perspective" "float fov" [{_floats([cam["fov"]])}]',
        "WorldBegin",
    ]
    for pl in desc.get("point_lights", []):
        out.append(
            f'LightSource "point" "rgb I" [{_floats(pl["I"])}] '
            f'"point from" [{_floats(pl["from"])}]'
        )
    for m in desc["meshes"]:
        out.append("AttributeBegin")
        if m.get("L") is not None:
            out.append(f'AreaLightSource "diffuse" "rgb L" [{_floats(m["L"])}]')
        out.append(f'Material "matte" "rgb Kd" [{_floats(m["Kd"])}]')
        if m.get("ply"):
            ply = f'{stem}-{m["name"]}.ply'
            write_ply(os.path.join(out_dir, ply), m["P"], m["indices"], m.get("N"))
            out.append(f'Shape "plymesh" "string filename" ["{ply}"]')
        else:
            shape = (
                f'Shape "trianglemesh" "integer indices" '
                f'[{" ".join(str(int(i)) for i in np.asarray(m["indices"]).reshape(-1))}] '
                f'"point P" [{_floats(m["P"])}]'
            )
            if m.get("N") is not None:
                shape += f' "normal N" [{_floats(m["N"])}]'
            out.append(shape)
        out.append("AttributeEnd")
    out.append("WorldEnd")
    path = os.path.join(out_dir, stem + ".pbrt")
    with open(path, "w") as fh:
        fh.write("\n".join(out) + "\n")
    return path
