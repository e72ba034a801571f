"""Cameras: host-side construction + device-side batched ray generation.

Capability match for pbrt-v3 src/cameras/ (perspective, orthographic,
environment, realistic) and src/core/camera.{h,cpp}. The projective
transform chain (screen window -> raster -> camera) is built on the host
exactly as in ProjectiveCamera's constructor; the device side is a single
vectorized ray-gen over a batch of CameraSamples (film + lens points), with
depth of field via concentric lens sampling.

The realistic camera's lens-element tracing is approximated by the thin-lens
model (same params: aperture + focus); full element tables are a later
extension (SURVEY.md §7 stage 9).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from tpu_pbrt.core import transform as xf
from tpu_pbrt.core.sampling import concentric_sample_disk
from tpu_pbrt.core.vecmath import dot, linear3, normalize
from tpu_pbrt.utils.error import Error, Warning

CAM_PERSPECTIVE = 0
CAM_ORTHOGRAPHIC = 1
CAM_ENVIRONMENT = 2
CAM_REALISTIC = 3


class CompiledCamera(NamedTuple):
    """Device-ready camera. Matrices are float32 (4,4); row-vector math is
    done explicitly in generate_rays. For CAM_REALISTIC, `lens` carries
    the compiled element stack (cameras/realistic.py) and the projective
    matrices hold a thin-lens PROXY (fov from the focused film distance)
    used only by the pinhole-approximated seams (ray differentials,
    BDPT t=1 / light-tracing We — pbrt's realistic camera does not
    implement We/Sample_Wi at all; the proxy is our loud stand-in)."""

    cam_type: int  # static python int — selects the trace path
    raster_to_camera: jnp.ndarray  # (4,4)
    camera_to_world: jnp.ndarray  # (4,4)
    lens_radius: jnp.ndarray  # scalar
    focal_distance: jnp.ndarray  # scalar
    shutter_open: float
    shutter_close: float
    full_res: tuple  # (x, y)
    lens: object = None  # CompiledLens for CAM_REALISTIC


def _screen_window(aspect: float, params) -> tuple:
    sw = params.find_float("screenwindow")
    if aspect > 1.0:
        screen = [-aspect, aspect, -1.0, 1.0]
    else:
        screen = [-1.0, 1.0, -1.0 / aspect, 1.0 / aspect]
    if sw is not None:
        if len(sw) == 4:
            screen = [sw[0], sw[1], sw[2], sw[3]]
        else:
            Error('"screenwindow" should have four values')
    return screen


def make_camera(name: str, params, cam_to_world: xf.Transform, full_res,
                shutter=(0.0, 1.0), film_diag: float = 0.035,
                scene_dir: str = "."):
    """api.cpp MakeCamera: string-dispatched factory -> CompiledCamera."""
    res_x, res_y = full_res
    aspect = params.find_one_float("frameaspectratio", res_x / res_y)
    lens_radius = params.find_one_float("lensradius", 0.0)
    focal = params.find_one_float("focaldistance", 1e6)
    lens = None

    if name in ("perspective", "realistic"):
        if name == "realistic":
            # real lens-element tracing (cameras/realistic.py). The
            # projective matrices built below become the thin-lens PROXY
            # for the pinhole-approximated seams (see CompiledCamera).
            import math as _math

            from tpu_pbrt.cameras.realistic import (
                apply_aperture_diameter,
                builtin_doublet,
                compile_lens,
                parse_lens_file,
            )
            from tpu_pbrt.utils.fileutil import resolve_filename

            ap_diam = params.find_one_float("aperturediameter", 1.0) / 1000.0
            focal = params.find_one_float("focusdistance", 10.0)
            lens_file = params.find_one_string("lensfile", "")
            rows = None
            if lens_file:
                try:
                    rows = parse_lens_file(
                        resolve_filename(lens_file, scene_dir)
                    )
                    # realistic.cpp: "aperturediameter" rescales the
                    # prescription's aperture-stop element (clamped to
                    # the stop's physical bound)
                    rows = apply_aperture_diameter(rows, ap_diam)
                except Exception as e:  # noqa: BLE001
                    Warning(
                        f'realistic: could not read lensfile "{lens_file}" '
                        f"({e}); using the built-in doublet"
                    )
            if rows is None:
                rows = builtin_doublet(ap_diam=max(ap_diam, 1e-4))
            lens = compile_lens(rows, focal, film_diag)
            ctype = CAM_REALISTIC
            # proxy fov from the focused film distance (2 atan(diag/2z))
            fov = _math.degrees(
                2.0 * _math.atan(0.5 * film_diag / max(lens.rear_z, 1e-4))
            )
            lens_radius = ap_diam / 2.0
        else:
            fov = params.find_one_float("fov", 90.0)
            halffov = params.find_one_float("halffov", -1.0)
            if halffov > 0:
                fov = 2.0 * halffov
            ctype = CAM_PERSPECTIVE
        screen = _screen_window(aspect, params)
        cam_to_screen = xf.perspective(fov, 1e-2, 1000.0)
    elif name == "orthographic":
        screen = _screen_window(aspect, params)
        cam_to_screen = xf.orthographic(0.0, 1.0)
        ctype = CAM_ORTHOGRAPHIC
    elif name == "environment":
        screen = [-1.0, 1.0, -1.0, 1.0]
        cam_to_screen = xf.Transform()
        ctype = CAM_ENVIRONMENT
    else:
        Warning(f'Camera "{name}" unknown; using "perspective".')
        return make_camera("perspective", params, cam_to_world, full_res, shutter)

    x0, x1, y0, y1 = screen
    screen_to_raster = (
        xf.scale(res_x, res_y, 1.0)
        * xf.scale(1.0 / (x1 - x0), 1.0 / (y0 - y1), 1.0)
        * xf.translate([-x0, -y1, 0.0])
    )
    raster_to_screen = screen_to_raster.inverse()
    raster_to_camera = cam_to_screen.inverse() * raster_to_screen

    return CompiledCamera(
        cam_type=ctype,
        raster_to_camera=jnp.asarray(raster_to_camera.m, jnp.float32),
        camera_to_world=jnp.asarray(cam_to_world.m, jnp.float32),
        lens_radius=jnp.float32(lens_radius),
        focal_distance=jnp.float32(focal),
        shutter_open=shutter[0],
        shutter_close=shutter[1],
        full_res=(res_x, res_y),
        lens=lens,
    )


def _xform_point(m, p):
    r = linear3(m[:3, :3], p) + m[:3, 3]
    w = dot(p, m[3, :3]) + m[3, 3]
    return r / jnp.where(w == 0.0, 1.0, w)[..., None]


def _xform_vector(m, v):
    return linear3(m[:3, :3], v)


def _screen_area_z1(cam: CompiledCamera):
    """Area of the perspective screen window projected to the z=1 plane in
    camera space (perspective.cpp PerspectiveCamera constructor's A)."""
    rx, ry = cam.full_res
    corners = jnp.asarray([[0.0, 0.0, 0.0], [rx, ry, 0.0]], jnp.float32)
    p = _xform_point(cam.raster_to_camera, corners)
    p = p / p[:, 2:3]
    return jnp.abs((p[1, 0] - p[0, 0]) * (p[1, 1] - p[0, 1]))


def camera_world_frame(cam: CompiledCamera):
    """(origin, forward) of the camera in world space."""
    o = _xform_point(cam.camera_to_world, jnp.zeros((1, 3), jnp.float32))[0]
    fwd = normalize(
        _xform_vector(cam.camera_to_world, jnp.asarray([[0.0, 0.0, 1.0]], jnp.float32))
    )[0]
    return o, fwd


def project_to_raster(cam: CompiledCamera, p_world):
    """World point -> raster coordinates + in-front/in-bounds mask (the
    inverse of generate_rays for the pinhole perspective camera; used by
    BDPT's t=1 camera connections and by light tracing)."""
    w2c = jnp.linalg.inv(cam.camera_to_world)
    c2r = jnp.linalg.inv(cam.raster_to_camera)
    p_cam = _xform_point(w2c, p_world)
    in_front = p_cam[..., 2] > 1e-6
    p_safe = jnp.where(in_front[..., None], p_cam, jnp.ones_like(p_cam))
    p_ras = _xform_point(c2r, p_safe)
    rx, ry = cam.full_res
    in_b = (
        in_front
        & (p_ras[..., 0] >= 0.0)
        & (p_ras[..., 0] < rx)
        & (p_ras[..., 1] >= 0.0)
        & (p_ras[..., 1] < ry)
    )
    return p_ras[..., :2], in_b


def camera_pdf_we(cam: CompiledCamera, d_world):
    """PerspectiveCamera::Pdf_We: (pdf_pos, pdf_dir) of generating a ray
    in direction d_world. Delta pinhole position -> pdf_pos = 1."""
    _, fwd = camera_world_frame(cam)
    a = _screen_area_z1(cam)
    cos_t = jnp.maximum(jnp.sum(d_world * fwd, axis=-1), 0.0)
    pdf_dir = jnp.where(
        cos_t > 1e-6, 1.0 / (a * jnp.maximum(cos_t, 1e-9) ** 3), 0.0
    )
    return jnp.ones_like(pdf_dir), pdf_dir


def camera_sample_wi(cam: CompiledCamera, ref_p):
    """PerspectiveCamera::Sample_Wi for a pinhole lens: direction to the
    camera, distance, solid-angle pdf, and the importance We carried by
    that connection (perspective.cpp:260). Returns
    (wi, dist, pdf, we (R,), raster_xy, in_bounds)."""
    cam_p, fwd = camera_world_frame(cam)
    a = _screen_area_z1(cam)
    to_cam = cam_p - ref_p
    dist = jnp.maximum(jnp.linalg.norm(to_cam, axis=-1), 1e-12)
    wi = to_cam / dist[..., None]
    cos_t = jnp.maximum(jnp.sum(-wi * fwd, axis=-1), 0.0)  # ray cam->ref
    # pinhole: lensArea treated as 1 (delta), pdf in solid angle at ref
    pdf = dist * dist / jnp.maximum(cos_t, 1e-9)
    we = jnp.where(cos_t > 1e-6, 1.0 / (a * jnp.maximum(cos_t, 1e-9) ** 4), 0.0)
    raster, in_b = project_to_raster(cam, ref_p)
    we = jnp.where(in_b, we, 0.0)
    return wi, dist, pdf, we, raster, in_b


def generate_rays(cam: CompiledCamera, p_film, u_lens):
    """Batched Camera::GenerateRay.

    p_film: (...,2) raster-space sample points; u_lens: (...,2) in [0,1).
    Returns (o, d, weight): world-space origins/directions + ray weight."""
    p_raster = jnp.concatenate([p_film, jnp.zeros_like(p_film[..., :1])], axis=-1)
    p_cam = _xform_point(cam.raster_to_camera, p_raster)

    if cam.cam_type == CAM_REALISTIC:
        # realistic.cpp GenerateRay: raster -> physical film point
        # (x negated, pbrt's film orientation), exit-pupil sample,
        # element-stack trace; vignetted lanes carry weight 0.
        from tpu_pbrt.cameras.realistic import sample_pupil, trace_lenses

        lens = cam.lens
        rx, ry = cam.full_res
        a = ry / rx
        fx = np.float32(np.sqrt(lens.film_diag**2 / (1.0 + a * a)))
        fy = np.float32(a * fx)
        sx = p_film[..., 0] / rx
        sy = p_film[..., 1] / ry
        pf = jnp.stack(
            [-(sx - 0.5) * fx, (sy - 0.5) * fy,
             jnp.zeros_like(sx)], axis=-1,
        )
        p_rear, area = sample_pupil(lens, pf, u_lens)
        d0 = normalize(p_rear - pf)
        ok, o_c, d_c = trace_lenses(lens, pf, d0)
        cos4 = jnp.maximum(d0[..., 2], 0.0) ** 4
        # exposure-normalized simple weighting (realistic.cpp's
        # simpleWeighting, divided by the on-axis reference so a stopped
        # -down lens meters like the thin-lens camera): cos^4 * A(r)/A(0)
        area0 = (lens.pupil[0, 2] - lens.pupil[0, 0]) * (
            lens.pupil[0, 3] - lens.pupil[0, 1]
        )
        weight = jnp.where(
            ok, cos4 * area / jnp.maximum(area0, 1e-20), 0.0
        )
        o_w = _xform_point(cam.camera_to_world, o_c)
        d_w = normalize(_xform_vector(cam.camera_to_world, d_c))
        return o_w, d_w, weight

    if cam.cam_type == CAM_PERSPECTIVE:
        o = jnp.zeros_like(p_cam)
        d = normalize(p_cam)
    elif cam.cam_type == CAM_ORTHOGRAPHIC:
        o = p_cam
        d = jnp.broadcast_to(jnp.asarray([0.0, 0.0, 1.0], jnp.float32), p_cam.shape)
    else:  # environment: lat-long over the full sphere (pbrt environment.cpp)
        x, y = p_film[..., 0], p_film[..., 1]
        theta = jnp.pi * y / cam.full_res[1]
        phi = 2.0 * jnp.pi * x / cam.full_res[0]
        d = jnp.stack(
            [jnp.sin(theta) * jnp.cos(phi), jnp.cos(theta), jnp.sin(theta) * jnp.sin(phi)],
            axis=-1,
        )
        o = jnp.zeros_like(d)

    if cam.cam_type != CAM_ENVIRONMENT:
        # thin-lens depth of field (ProjectiveCamera lens code)
        def with_lens(o, d):
            lx, ly = concentric_sample_disk(u_lens[..., 0], u_lens[..., 1])
            p_lens = cam.lens_radius * jnp.stack([lx, ly], axis=-1)
            ft = cam.focal_distance / jnp.where(d[..., 2] == 0.0, 1.0, d[..., 2])
            p_focus = o + ft[..., None] * d
            o_new = jnp.concatenate([p_lens, jnp.zeros_like(p_lens[..., :1])], axis=-1)
            # orthographic keeps its z origin
            o_new = o_new + o * jnp.asarray([0.0, 0.0, 1.0], jnp.float32)
            d_new = normalize(p_focus - o_new)
            return o_new, d_new

        o_l, d_l = with_lens(o, d)
        use_lens = cam.lens_radius > 0.0
        o = jnp.where(use_lens, o_l, o)
        d = jnp.where(use_lens, d_l, d)

    o_w = _xform_point(cam.camera_to_world, o)
    d_w = normalize(_xform_vector(cam.camera_to_world, d))
    weight = jnp.ones(p_film.shape[:-1], jnp.float32)
    return o_w, d_w, weight


def ray_differentials(cam: CompiledCamera, p_film):
    """Camera::GenerateRayDifferential's offset-ray deltas (camera.cpp):
    world-space (d_origin/dx, d_dir/dx, d_origin/dy, d_dir/dy) for a
    +1-raster-pixel step. Pinhole-analytic; the thin-lens origin jitter
    is ignored exactly as pbrt's differentials assume the primary ray."""
    zero = jnp.zeros(p_film.shape[:-1] + (3,), jnp.float32)
    if cam.cam_type == CAM_ENVIRONMENT:
        x, y = p_film[..., 0], p_film[..., 1]

        def dir_at(xx, yy):
            theta = jnp.pi * yy / cam.full_res[1]
            phi = 2.0 * jnp.pi * xx / cam.full_res[0]
            d = jnp.stack(
                [jnp.sin(theta) * jnp.cos(phi), jnp.cos(theta),
                 jnp.sin(theta) * jnp.sin(phi)], axis=-1)
            return normalize(_xform_vector(cam.camera_to_world, d))

        base = dir_at(x, y)
        return (zero, dir_at(x + 1.0, y) - base,
                zero, dir_at(x, y + 1.0) - base)

    p_raster = jnp.concatenate(
        [p_film, jnp.zeros_like(p_film[..., :1])], axis=-1)
    p_cam = _xform_point(cam.raster_to_camera, p_raster)
    # raster steps as PROJECTED POINT DIFFERENCES (camera.cpp shifts the
    # CameraSample by one pixel): raster_to_camera is projective, so
    # pushing the step through the linear part alone mis-scales it
    step_x = jnp.asarray([1.0, 0.0, 0.0], jnp.float32)
    step_y = jnp.asarray([0.0, 1.0, 0.0], jnp.float32)
    dx_cam = _xform_point(cam.raster_to_camera, p_raster + step_x) - p_cam
    dy_cam = _xform_point(cam.raster_to_camera, p_raster + step_y) - p_cam
    # realistic: the thin-lens proxy matrices stand in for the primary
    # ray's differentials (pbrt likewise assumes the unperturbed ray)
    if cam.cam_type in (CAM_PERSPECTIVE, CAM_REALISTIC):
        d0 = normalize(p_cam)
        ddx = _xform_vector(cam.camera_to_world, normalize(p_cam + dx_cam) - d0)
        ddy = _xform_vector(cam.camera_to_world, normalize(p_cam + dy_cam) - d0)
        return zero, ddx, zero, ddy
    # orthographic: direction constant, origin shifts
    dox = _xform_vector(cam.camera_to_world, dx_cam)
    doy = _xform_vector(cam.camera_to_world, dy_cam)
    return dox, zero, doy, zero
