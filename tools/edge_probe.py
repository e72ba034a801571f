#!/usr/bin/env python3
"""On-chip probe of the ray-triangle tests' edge behaviour (ISSUE 27).

    python3 tools/edge_probe.py cornell   # the brute path, scenes/cornell-path.pbrt
    python3 tools/edge_probe.py product   # what the feature product's precision is
    python3 tools/edge_probe.py stream [N]  # the stream tracer's leaf test, killeroo-class (N: an NxN grid)

`cornell`: all 65,536 pixel centres of the file's camera (the program's own
rays, `cameras.generate_rays` on the device, set against float64's: the
`camera` and `program_with_its_camera` lines), and one shadow ray from each
first hit towards a point on the light quad, through the program's
closest-hit (`integrators/common.py::_closest_hit`, whatever the scene's
acceleration table is) and through the plain oracle
`accel/traverse.py::brute_force_intersect`, both on the device jax chose, both
against a float64 Moeller-Trumbore on the host. A ray is DECIDED where the
float64 test gives the same answer with every triangle widened and narrowed
by `BAND` in its barycentrics (and the ray's interval with it): on a decided ray a hit / miss or a winner
other than float64's is a wrong answer, on the others it is the edge band
doing what it is there for. Counts wrong answers by image row.

`product`: the feature product `phi @ W` as the program forms it, under each
precision jax can ask for, against the float64 product of the same float32
operands: the largest error in units of sum_k |phi_k W_k|, as bits.

`stream`: killeroo-class (the benchmark's own configuration and writer) at
its 512x512 pixel centres, `stream_intersect` against a plain element-wise
float32 test of every pair on the device; rays whose answers differ are
settled by float64 over all triangles.

Prints one JSON line per reading; meant for the chip (`chiprun -- python3
tools/edge_probe.py ...`), runs on the CPU too (slowly, and proves nothing
about the chip).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: half-width, in barycentric units, of the band inside which float32 tests
#: may disagree with float64 (ten times mxu.EDGE_EPS)
BAND = 1e-5


def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def mt64(tris, o, d, t_max, band=0.0, block=4096):
    """float64 Moeller-Trumbore, every ray against every triangle ->
    (t, prim), prim -1 on a miss. `band` widens (> 0) or narrows (< 0)
    every triangle in its barycentrics, and the ray's interval by ten
    times as much (relative at `t_max`, absolute at 0)."""
    tris = np.asarray(tris, np.float64)
    v0, e1, e2 = tris[:, 0], tris[:, 1] - tris[:, 0], tris[:, 2] - tris[:, 0]
    o, d = np.asarray(o, np.float64), np.asarray(d, np.float64)
    t_max = np.broadcast_to(np.asarray(t_max, np.float64), o.shape[:1])
    t_out = np.full(len(o), np.inf)
    k_out = np.full(len(o), -1, np.int64)
    step = max(1, block * 4096 // max(len(tris), 1))
    for a in range(0, len(o), step):
        oo, dd = o[a:a + step, None, :], d[a:a + step, None, :]
        p = np.cross(dd, e2[None])
        det = np.sum(e1[None] * p, -1)
        with np.errstate(divide="ignore", invalid="ignore"):
            inv = 1.0 / det
            s = oo - v0[None]
            u = np.sum(s * p, -1) * inv
            q = np.cross(s, e1[None])
            v = np.sum(dd * q, -1) * inv
            t = np.sum(e2[None] * q, -1) * inv
        ok = ((det != 0) & (u >= -band) & (v >= -band) & (u + v <= 1 + band)
              & (t > -10 * band) & (t < t_max[a:a + step, None] * (1 + 10 * band)))
        t = np.where(ok, t, np.inf)
        k = np.argmin(t, axis=1)
        tb = t[np.arange(len(k)), k]
        t_out[a:a + step] = tb
        k_out[a:a + step] = np.where(np.isfinite(tb), k, -1)
    return t_out, k_out


def judge(tris, o, d, t_max, prim):
    """-> (wrong (R,) bool, decided (R,) bool) for a tracer's winners
    `prim`. Wrong: the ray is decided and the tracer's hit / miss differs
    from float64's, or its winner is another triangle whose float64
    distance along the ray is not float64's closest to 1e-5 relative."""
    t0, k0 = mt64(tris, o, d, t_max)
    _, kw = mt64(tris, o, d, t_max, band=BAND)
    _, kn = mt64(tris, o, d, t_max, band=-BAND)
    decided = (k0 == kw) & (k0 == kn)
    prim = np.asarray(prim)
    flip = (prim >= 0) != (k0 >= 0)
    other = (prim >= 0) & (k0 >= 0) & (prim != k0)
    if other.any():
        # a tie between coplanar or edge-sharing triangles is no fault
        idx = np.flatnonzero(other)
        tri = np.asarray(tris, np.float64)[prim[idx]]
        tt = plane_t64(tri, o[idx], d[idx])
        same_t = np.abs(tt - t0[idx]) <= 1e-5 * np.abs(t0[idx])
        other[idx[same_t]] = False
    return (flip | other) & decided, decided


def plane_t64(tri, o, d):
    """float64 distance of ray i to the plane of ITS triangle tri[i]."""
    tri, o, d = (np.asarray(a, np.float64) for a in (tri, o, d))
    e1, e2 = tri[:, 1] - tri[:, 0], tri[:, 2] - tri[:, 0]
    n = np.cross(e1, e2)
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.sum((tri[:, 0] - o) * n, -1) / np.sum(d * n, -1)


def mt32_device(tris, slab: int, tri_block: int):
    """A jitted element-wise float32 Moeller-Trumbore on the device, with no
    gather and no matrix product -> f(o, d, t_max) -> (t, prim). The yardstick
    where the oracle's per-pair axis permutation would be too slow."""
    import jax
    import jax.numpy as jnp

    tris = np.asarray(tris, np.float32)
    pad = (-len(tris)) % tri_block
    if pad:  # zero-area triangles never hit
        tris = np.concatenate([tris, np.zeros((pad, 3, 3), np.float32)])
    tabs = [np.ascontiguousarray(a.reshape(-1, tri_block, 3).transpose(0, 2, 1))
            for a in (tris[:, 0], tris[:, 1] - tris[:, 0], tris[:, 2] - tris[:, 0])]

    def rays(args):
        o, d, t_max = args
        ox, oy, oz = (o[:, i:i + 1] for i in range(3))
        dx, dy, dz = (d[:, i:i + 1] for i in range(3))

        def step(carry, tab):
            t_best, k_best, base = carry
            a, b, c = tab  # v0, e1, e2: (3, tri_block)
            px, py, pz = dy * c[2] - dz * c[1], dz * c[0] - dx * c[2], dx * c[1] - dy * c[0]
            det = b[0] * px + b[1] * py + b[2] * pz
            inv = 1.0 / jnp.where(det == 0.0, 1.0, det)
            sx, sy, sz = ox - a[0], oy - a[1], oz - a[2]
            u = (sx * px + sy * py + sz * pz) * inv
            qx, qy, qz = sy * b[2] - sz * b[1], sz * b[0] - sx * b[2], sx * b[1] - sy * b[0]
            v = (dx * qx + dy * qy + dz * qz) * inv
            t = (c[0] * qx + c[1] * qy + c[2] * qz) * inv
            ok = (det != 0.0) & (u >= 0.0) & (v >= 0.0) & (u + v <= 1.0) & (t > 0.0) & (t < t_max[:, None])
            t = jnp.where(ok, t, jnp.inf)
            k = jnp.argmin(t, axis=1).astype(jnp.int32)
            tb = jnp.min(t, axis=1)
            better = tb < t_best
            return (jnp.where(better, tb, t_best), jnp.where(better, k + base, k_best), base + tri_block), None

        init = (jnp.full(o.shape[:1], jnp.inf, jnp.float32), jnp.full(o.shape[:1], -1, jnp.int32), jnp.int32(0))
        (t, k, _), _ = jax.lax.scan(step, init, tuple(jnp.asarray(x) for x in tabs))
        return t, k

    @jax.jit
    def f(o, d, t_max):
        t_max = jnp.broadcast_to(jnp.asarray(t_max, jnp.float32), o.shape[:1])
        t, k = jax.lax.map(rays, (o.reshape(-1, slab, 3), d.reshape(-1, slab, 3), t_max.reshape(-1, slab)))
        return t.reshape(-1), k.reshape(-1)

    return f


def by_row(wrong, rows) -> dict:
    r = np.asarray(rows)[np.asarray(wrong)]
    return {int(k): int(v) for k, v in zip(*np.unique(r, return_counts=True))}


def device_line() -> None:
    import jax

    dev = jax.devices()[0]
    say(what="device", platform=dev.platform, kind=dev.device_kind, count=len(jax.devices()),
        jax=jax.__version__)


def cornell_rays(scene):
    """The program's camera rays at the pixel centres, from the device jax
    chose -> (o, d, image row of each) as numpy."""
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.cameras import generate_rays

    xres, yres = scene.film.full_resolution
    k = np.arange(xres * yres)
    pf = np.stack([(k % xres) + 0.5, (k // xres) + 0.5], -1).astype(np.float32)
    o, d, _ = jax.jit(lambda p: generate_rays(scene.camera, p, jnp.zeros_like(p)))(pf)
    return np.asarray(o), np.asarray(d), k // xres


def true_camera_rays(scene):
    """The same rays from the camera's two matrices in float64 on the host
    (a pinhole perspective camera) -> (o, d, radians a pixel)."""
    cam = scene.camera
    r2c = np.asarray(cam.raster_to_camera, np.float64)
    c2w = np.asarray(cam.camera_to_world, np.float64)
    xres, yres = scene.film.full_resolution
    k = np.arange(xres * yres)
    pr = np.stack([(k % xres) + 0.5, (k // xres) + 0.5, np.zeros(len(k)), np.ones(len(k))], -1)
    pc = pr @ r2c.T
    pc = pc[:, :3] / pc[:, 3:4]
    d = pc / np.linalg.norm(pc, axis=-1, keepdims=True)
    d = d @ c2w[:3, :3].T
    o = np.broadcast_to(c2w[:3, 3], d.shape)
    step = (np.array([0.5 * xres + 1, 0.5 * yres, 0, 1]) @ r2c.T) - (np.array([0.5 * xres, 0.5 * yres, 0, 1]) @ r2c.T)
    return o, d / np.linalg.norm(d, axis=-1, keepdims=True), float(np.linalg.norm(step[:3]))


def shadow_rays(tris, light, o, d):
    """From each camera ray's float64 first hit, offset along the facing
    normal, towards a point of the light quad (two triangles `light`)
    drawn from a Weyl sequence -> (o, d, t_max, has) float32."""
    t, k = mt64(tris, o, d, np.inf)
    has = k >= 0
    kk = np.maximum(k, 0)
    tri = np.asarray(tris, np.float64)[kk]
    n = np.cross(tri[:, 1] - tri[:, 0], tri[:, 2] - tri[:, 0])
    n /= np.maximum(np.linalg.norm(n, axis=-1, keepdims=True), 1e-30)
    p = o + np.where(has, t, 0.0)[:, None] * d
    n = np.where((np.sum(n * d, -1) > 0)[:, None], -n, n)
    i = np.arange(len(o), dtype=np.float64)
    u1, u2 = (i * 0.6180339887498949) % 1.0, (i * 0.7548776662466927) % 1.0
    lt = np.asarray(tris, np.float64)[light[(i.astype(np.int64)) % len(light)]]
    su = np.sqrt(u1)
    target = (1 - su)[:, None] * lt[:, 0] + (su * (1 - u2))[:, None] * lt[:, 1] + (su * u2)[:, None] * lt[:, 2]
    so = p + 1e-4 * n
    to = target - so
    dist = np.linalg.norm(to, axis=-1)
    sd = to / np.maximum(dist, 1e-30)[:, None]
    t_max = np.where(has, dist * 0.999, -1.0)
    return so.astype(np.float32), sd.astype(np.float32), t_max.astype(np.float32), has


def probe_cornell() -> int:
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.accel.traverse import brute_force_intersect
    from tpu_pbrt.integrators.common import _closest_hit
    from tpu_pbrt.scene.api import Options, compile_file

    device_line()
    scene, _ = compile_file(os.path.join(ROOT, "scenes", "cornell-path.pbrt"), Options(quiet=True))
    dev = scene.dev
    n = int(scene.n_tris)
    tris = np.asarray(dev["tri_verts"])[:n]
    say(what="scene", triangles=n, table=sorted(k for k in ("brute", "bfeat", "tstream", "tpack", "bvh", "wbvh") if k in dev))
    program = jax.jit(lambda o, d, tm: _closest_hit(dev, o, d, tm, None).prim)
    oracle = jax.jit(lambda o, d, tm: brute_force_intersect(jnp.asarray(tris), o, d, tm, chunk=64).prim)

    plain = mt32_device(tris, 8192, len(tris))
    plain_prim = lambda o, d, tm: plain(o, d, tm)[1]  # noqa: E731

    o, d, rows = cornell_rays(scene)
    inf = np.full(len(o), np.inf, np.float32)
    light = np.flatnonzero(np.abs(tris[:, :, 1] - 0.998).max(axis=1) < 1e-6)
    so, sd, st, has = shadow_rays(tris, light, o, d)
    # the camera: the device's rays against float64's, in pixels
    to, td, pixel = true_camera_rays(scene)
    cosang = np.clip(np.sum(d.astype(np.float64) * td, -1) / np.linalg.norm(d.astype(np.float64), axis=-1), -1, 1)
    off = np.sqrt(np.maximum(2 - 2 * cosang, 0)) / pixel  # chord ~ angle; arccos has no digits left here
    say(what="camera", n=len(o), ray_error_pixels_max=float(off.max()), ray_error_pixels_median=float(np.median(off)),
        origin_error_max=float(np.abs(o - to).max()))
    # camera and tracer together: the program's answer a pixel centre
    # against float64's on the TRUE ray of that pixel centre
    prim = np.asarray(program(jnp.asarray(o), jnp.asarray(d), jnp.asarray(inf)))
    wrong, decided = judge(tris, to, td, inf, prim)
    say(what="cornell", rays="camera", tracer="program_with_its_camera", n=len(o), decided=int(decided.sum()),
        wrong=int(wrong.sum()), wrong_hits_light=int((wrong & np.isin(prim, light)).sum()),
        wrong_by_row=by_row(wrong, rows))
    bad = int(wrong.sum())
    for kind, (ro, rd, rt) in {"camera": (o, d, inf), "shadow": (so, sd, st)}.items():
        for name, fn in (("program", program), ("oracle", oracle), ("plain_float32", plain_prim)):
            prim = np.asarray(fn(jnp.asarray(ro), jnp.asarray(rd), jnp.asarray(rt)))
            wrong, decided = judge(tris, ro, rd, rt, prim)
            say(what="cornell", rays=kind, tracer=name, n=len(ro), decided=int(decided.sum()),
                wrong=int(wrong.sum()), hits=int((prim >= 0).sum()),
                wrong_hits_light=int((wrong & np.isin(prim, light)).sum()),
                wrong_by_row=by_row(wrong, rows))
            if name == "program":
                bad += int(wrong.sum())
    return 1 if bad else 0


def probe_product() -> int:
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.accel.mxu import ray_features, tri_feature_weights_raw
    from tpu_pbrt.scene.api import Options, compile_file

    device_line()
    scene, _ = compile_file(os.path.join(ROOT, "scenes", "cornell-path.pbrt"), Options(quiet=True))
    n = int(scene.n_tris)
    tris = np.asarray(scene.dev["tri_verts"])[:n]
    center = np.asarray(scene.world_center, np.float32)
    w = tri_feature_weights_raw(tris, center)  # (T, 16, 4): the brute path's product until PR 27
    feat = np.ascontiguousarray(w.transpose(1, 2, 0).reshape(16, 4 * n))  # (16, 4T) float32
    o, d, _ = cornell_rays(scene)
    phi = np.asarray(jax.jit(lambda o, d: ray_features(o - center, d))(o, d))
    exact = phi.astype(np.float64) @ feat.astype(np.float64)
    scale = np.abs(phi).astype(np.float64) @ np.abs(feat).astype(np.float64)

    P = jax.lax.Precision
    forms = {
        "matmul_default": lambda a, b: jnp.matmul(a, b),
        "matmul_high": lambda a, b: jnp.matmul(a, b, precision=P.HIGH),
        "matmul_highest": lambda a, b: jnp.matmul(a, b, precision=P.HIGHEST),
        "matmul_highest_in_map": lambda a, b: jax.lax.map(
            lambda x: jnp.matmul(x, b, precision=P.HIGHEST), a.reshape(2, -1, 16)).reshape(-1, b.shape[1]),
        "matmul_bf16_operands": lambda a, b: jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), preferred_element_type=jnp.float32),
        "elementwise_sum": lambda a, b: jnp.sum(a[:, :, None] * b[None], axis=1),
    }
    for preset in ("F32_F32_F32", "BF16_BF16_F32_X3", "BF16_BF16_F32_X6"):
        algo = getattr(jax.lax.DotAlgorithmPreset, preset, None)
        if algo is not None:
            forms["dot_" + preset] = (lambda a, b, algo=algo: jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())), precision=algo, preferred_element_type=jnp.float32))
    def reading(name, fn):
        try:
            got = np.asarray(jax.jit(fn)(jnp.asarray(phi), jnp.asarray(feat)), np.float64)
        except Exception as e:  # a preset this backend refuses
            say(what="product", form=name, error=f"{type(e).__name__}: {e}"[:200])
            return
        err = np.abs(got - exact) / np.maximum(scale, 1e-300)
        worst = float(err.max())
        say(what="product", form=name, worst_rel_err=worst,
            bits=(float(-np.log2(worst)) if worst > 0 else 64.0), median_rel_err=float(np.median(err)))

    for name, fn in forms.items():
        reading(name, fn)
    with jax.default_matmul_precision("highest"):
        reading("matmul_under_default_matmul_precision_highest", lambda a, b: jnp.matmul(a, b))
    # the camera's own product until PR 27: raster points times a 3x3, K = 3
    r2c = np.asarray(scene.camera.raster_to_camera, np.float32)[:3, :3]
    xres, yres = scene.film.full_resolution
    k = np.arange(xres * yres)
    pr = np.stack([(k % xres) + 0.37, (k // xres) + 0.61, np.zeros(len(k))], -1).astype(np.float32)
    want = pr.astype(np.float64) @ r2c.astype(np.float64).T
    mag = np.abs(pr).astype(np.float64) @ np.abs(r2c).astype(np.float64).T
    for name, fn in (("raster_matmul_default", lambda a, b: a @ b.T),
                     ("raster_element_wise", lambda a, b: a[:, 0:1] * b[:, 0] + a[:, 1:2] * b[:, 1] + a[:, 2:3] * b[:, 2])):
        got = np.asarray(jax.jit(fn)(jnp.asarray(pr), jnp.asarray(r2c)), np.float64)
        worst = float((np.abs(got - want) / np.maximum(mag, 1e-300)).max())
        say(what="product", form=name, worst_rel_err=worst, bits=(float(-np.log2(worst)) if worst > 0 else 64.0))
    return 0


def probe_stream(res: int = 0) -> int:
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run as harness

    from tpu_pbrt.accel.stream import stream_intersect
    from tpu_pbrt.cameras import generate_rays
    from tpu_pbrt.scene.api import Options, compile_file

    device_line()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    config = harness.load_json(ROOT, {c["name"]: c for c in bench["configs"]}["killeroo-class"]["file"])
    desc = harness.load_module("scenes", config["scene_writer"]).build(config, 2_000_000_011)
    work = os.path.join(ROOT, ".bench_work", "edge_probe")
    path = harness.load_module("", "scenedesc").write_scene(desc, work, "scene")
    scene, _ = compile_file(path, Options(quiet=True))
    dev = scene.dev
    n = int(scene.n_tris)
    tris = np.asarray(dev["tri_verts"])[:n]
    full = int(config["xresolution"])
    xres = yres = res or full  # `res`: a coarser grid of the same image, for a try-out
    k = np.arange(xres * yres)
    pf = (np.stack([(k % xres) + 0.5, (k // xres) + 0.5], -1) * (full / xres)).astype(np.float32)
    o, d, _ = jax.jit(lambda p: generate_rays(scene.camera, p, jnp.zeros_like(p)))(pf)
    stream = jax.jit(lambda o, d: stream_intersect(
        dev["tstream"], dev["tri_verts"], o, d, jnp.inf, tv9T=dev.get("tri_verts9T")))
    hs = stream(o, d)
    # the yardstick on the device is the gather-free float32 test: the
    # oracle permutes axes per (ray, triangle) pair, 3.4e10 pairs here
    to, po = (np.asarray(a) for a in mt32_device(tris, min(4096, len(k)), 4096)(o, d, jnp.inf))
    ts, ps = np.asarray(hs.t), np.asarray(hs.prim)
    flip = (ps >= 0) != (po >= 0)
    with np.errstate(invalid="ignore"):
        far = (ps >= 0) & (po >= 0) & (ps != po) & (np.abs(ts - to) > 1e-4 * np.abs(to))
    hit = (po >= 0).reshape(yres, xres)
    edge = np.zeros_like(hit)
    edge[:, 1:] |= hit[:, 1:] != hit[:, :-1]
    edge[:, :-1] |= hit[:, 1:] != hit[:, :-1]
    edge[1:] |= hit[1:] != hit[:-1]
    edge[:-1] |= hit[1:] != hit[:-1]
    say(what="stream", n=len(k), triangles=n, hits_stream=int((ps >= 0).sum()), hits_plain=int((po >= 0).sum()),
        silhouette_pixels=int(edge.sum()), differ_hit_miss=int(flip.sum()),
        differ_winner_and_distance=int(far.sum()),
        differ_on_silhouette=int((flip | far)[edge.reshape(-1)].sum()))
    idx = np.flatnonzero(flip | far)[:4096]
    if len(idx):
        on, dn = np.asarray(o)[idx], np.asarray(d)[idx]
        inf = np.full(len(idx), np.inf)
        ws, decided = judge(tris, on, dn, inf, ps[idx])
        wo, _ = judge(tris, on, dn, inf, po[idx])
        say(what="stream_settled", rays=len(idx), decided=int(decided.sum()), stream_wrong=int(ws.sum()),
            plain_wrong=int(wo.sum()), stream_wrong_by_row=by_row(ws, (idx // xres)))
        return 1 if ws.any() else 0
    return 0


def main() -> int:
    from tpu_pbrt.config import place_compile_cache

    place_compile_cache()
    probes = {"cornell": probe_cornell, "product": probe_product, "stream": probe_stream}
    if len(sys.argv) < 2 or sys.argv[1] not in probes:
        print(__doc__, file=sys.stderr)
        return 2
    return probes[sys.argv[1]](*map(int, sys.argv[2:]))


if __name__ == "__main__":
    sys.exit(main())
