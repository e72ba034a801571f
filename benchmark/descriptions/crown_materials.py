"""The scene description of `crown-materials`, and its writer: a `.pbrt`
file with metal, glass and matte meshes (the large ones in binary PLY) and
one `LightSource "infinite"` whose map this writer writes as a PFM.

The description is a dict:

    camera        {"eye", "look", "up": 3 floats each, "fov": degrees}
    film          {"xres", "yres"}
    spp, maxdepth integers
    sampler       the Sampler directive's name
    integrator    the Integrator directive's name
    meshes        list of {"name", "P": (V,3) float32 world space,
                  "indices": (F,3) int32, "N": (V,3) float32 or None,
                  "ply": bool, "material": one of
                    {"type": "matte", "Kd": 3 floats}
                    {"type": "metal", "eta": 3 floats, "k": 3 floats,
                     "roughness": float}  or, anisotropic,
                    {"type": "metal", "eta", "k", "uroughness": float,
                     "vroughness": float}  (pbrt's remaproughness, true)
                    {"type": "glass", "eta": float, "Kr": 3 floats,
                     "Kt": 3 floats}  (smooth: a delta BSDF)}
    env           {"map": (H, W, 3) float32, row 0 at theta = 0 (light-space
                  +z), column x at phi = 2 pi (x + 0.5) / W, pbrt's lat-long
                  convention; "rotate": [degrees, x, y, z], the `Rotate` that
                  takes light space to world space}

`scenes/crown_materials.py` builds it from the configuration and a seed;
`references/crown_materials.py` renders it directly. The same description
writes the same bytes.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _scenedesc():
    """The harness's matte description, for its PLY writer and float format."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scenedesc.py")
    spec = importlib.util.spec_from_file_location("bench_scenedesc_for_crown_materials", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_SD = _scenedesc()
_floats, write_ply = _SD._floats, _SD.write_ply


def write_pfm(path: str, img) -> None:
    """An RGB PFM: little-endian float32, its lines bottom-up (the format's
    own order; pbrt flips them back, so row 0 of `img` is the top line)."""
    img = np.asarray(img, "<f4")
    h, w = img.shape[:2]
    with open(path, "wb") as fh:
        fh.write(f"PF\n{w} {h}\n-1.0\n".encode("ascii"))
        fh.write(np.ascontiguousarray(img[::-1]).tobytes())


def _material(m: dict) -> str:
    t = m["type"]
    if t == "matte":
        return f'Material "matte" "rgb Kd" [{_floats(m["Kd"])}]'
    if t == "metal":
        rough = (f'"float uroughness" [{_floats([m["uroughness"]])}] "float vroughness" [{_floats([m["vroughness"]])}]'
                 if "uroughness" in m else f'"float roughness" [{_floats([m["roughness"]])}]')
        return f'Material "metal" "rgb eta" [{_floats(m["eta"])}] "rgb k" [{_floats(m["k"])}] {rough}'
    if t == "glass":
        return (f'Material "glass" "float eta" [{_floats([m["eta"]])}] "rgb Kr" [{_floats(m["Kr"])}] '
                f'"rgb Kt" [{_floats(m["Kt"])}]')
    raise ValueError(f"no material {t!r} in this description")


def write_scene(desc: dict, out_dir: str, stem: str) -> str:
    """Write `<out_dir>/<stem>.pbrt`, `<stem>-env.pfm` and `<stem>-<mesh>.ply`
    for meshes marked `ply`; returns the `.pbrt` path."""
    os.makedirs(out_dir, exist_ok=True)
    cam, film = desc["camera"], desc["film"]
    env = f"{stem}-env.pfm"
    write_pfm(os.path.join(out_dir, env), desc["env"]["map"])
    out = [
        f'Integrator "{desc["integrator"]}" "integer maxdepth" [{int(desc["maxdepth"])}]',
        f'Sampler "{desc["sampler"]}" "integer pixelsamples" [{int(desc["spp"])}]',
        'PixelFilter "box"',
        f'Film "image" "integer xresolution" [{int(film["xres"])}] '
        f'"integer yresolution" [{int(film["yres"])}] "string filename" [""]',
        f'LookAt {_floats(cam["eye"])}  {_floats(cam["look"])}  {_floats(cam["up"])}',
        f'Camera "perspective" "float fov" [{_floats([cam["fov"]])}]',
        "WorldBegin",
        "AttributeBegin",
        f'Rotate {_floats(desc["env"]["rotate"])}',
        f'LightSource "infinite" "string mapname" ["{env}"]',
        "AttributeEnd",
    ]
    for m in desc["meshes"]:
        out.append("AttributeBegin")
        out.append(_material(m["material"]))
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
