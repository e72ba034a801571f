"""Device-side vector math on SoA jnp arrays.

Capability match for pbrt-v3 src/core/geometry.h's vector/point/normal
operations, re-expressed TPU-first: no Vector3 classes — everything is a
float32 array whose last axis is xyz, so all ops vectorize over ray batches.
Also carries the robust-offset machinery standing in for src/core/efloat.h
(conservative fixed epsilons instead of running error intervals; see
offset_ray_origin).
"""

from __future__ import annotations

import jax.numpy as jnp

# float32 machine epsilon / 2 (pbrt MachineEpsilon)
MACHINE_EPS = 5.960464477539063e-08
ONE_MINUS_EPSILON = 0.99999994  # largest float32 < 1
INF = jnp.inf


def gamma(n: int) -> float:
    """pbrt gamma(n): bound on accumulated fp rounding error."""
    return (n * MACHINE_EPS) / (1 - n * MACHINE_EPS)


def dot(a, b):
    return jnp.sum(a * b, axis=-1)


def absdot(a, b):
    return jnp.abs(dot(a, b))


def cross(a, b):
    return jnp.cross(a, b)


def length_squared(v):
    return jnp.sum(v * v, axis=-1)


def length(v):
    return jnp.sqrt(length_squared(v))


def normalize(v):
    return v / jnp.maximum(length(v)[..., None], 1e-20)


def distance(a, b):
    return length(a - b)


def lerp(t, a, b):
    return (1.0 - t) * a + t * b


def face_forward(n, v):
    """Flip n to lie in the hemisphere of v (pbrt Faceforward)."""
    return jnp.where(dot(n, v)[..., None] < 0.0, -n, n)


def coordinate_system(v):
    """Branchless orthonormal basis (Duff et al. 2017), replacing pbrt's
    CoordinateSystem. v must be normalized. Returns (t, b)."""
    z = v[..., 2]
    sign = jnp.where(z >= 0.0, 1.0, -1.0)
    a = -1.0 / (sign + z)
    b = v[..., 0] * v[..., 1] * a
    t1 = jnp.stack(
        [1.0 + sign * v[..., 0] * v[..., 0] * a, sign * b, -sign * v[..., 0]], axis=-1
    )
    t2 = jnp.stack([b, sign + v[..., 1] * v[..., 1] * a, -v[..., 1]], axis=-1)
    return t1, t2


def spherical_direction(sin_theta, cos_theta, phi):
    return jnp.stack(
        [sin_theta * jnp.cos(phi), sin_theta * jnp.sin(phi), cos_theta], axis=-1
    )


def spherical_theta(v):
    return jnp.arccos(jnp.clip(v[..., 2], -1.0, 1.0))


def spherical_phi(v):
    p = jnp.arctan2(v[..., 1], v[..., 0])
    return jnp.where(p < 0.0, p + 2.0 * jnp.pi, p)


def linear3(m, v):
    """The 3x3 matrix `m` applied to vectors v (...,3): what `v @ m.T`
    says, written out element-wise in float32.

    Never `@` for a small fixed transform: on the TPU a float32 matmul at
    the default precision is ONE bf16 pass (8 bits of each operand, 7.5
    bits measured on the v5e against 22.4 for `Precision.HIGHEST`), which
    snapped camera rays to a lattice coarser than a pixel (PERF.md,
    Findings PR 27). Three multiply-adds a component need no MXU."""
    return (v[..., 0:1] * m[:, 0] + v[..., 1:2] * m[:, 1]) + v[..., 2:3] * m[:, 2]


def to_local(v, t, b, n):
    """World -> shading frame (pbrt BSDF::WorldToLocal)."""
    return jnp.stack([dot(v, t), dot(v, b), dot(v, n)], axis=-1)


def to_world(v, t, b, n):
    return (
        v[..., 0:1] * t + v[..., 1:2] * b + v[..., 2:3] * n
    )


def reflect(wo, n):
    """pbrt Reflect: mirror wo about n (both pointing away from surface)."""
    return -wo + 2.0 * dot(wo, n)[..., None] * n


def refract(wi, n, eta):
    """pbrt Refract. Returns (refracted_dir, total_internal_reflection_mask).
    eta = eta_i/eta_t (scalar or batched); n on same side as wi."""
    eta = jnp.asarray(eta)
    cos_theta_i = dot(n, wi)
    sin2_theta_i = jnp.maximum(0.0, 1.0 - cos_theta_i * cos_theta_i)
    sin2_theta_t = eta * eta * sin2_theta_i
    tir = sin2_theta_t >= 1.0
    cos_theta_t = jnp.sqrt(jnp.maximum(0.0, 1.0 - sin2_theta_t))
    wt = eta[..., None] * -wi + (eta * cos_theta_i - cos_theta_t)[..., None] * n
    return wt, tir


def offset_ray_origin(p, n, d):
    """Robust shadow/secondary ray origin.

    pbrt's OffsetRayOrigin uses per-intersection error bounds from EFloat;
    the TPU build uses a conservative scale-adaptive epsilon (SURVEY.md §7
    'efloat machinery becomes fixed conservative epsilons'): offset along the
    geometric normal proportional to |p|, in the hemisphere of d."""
    eps = 1e-4 * jnp.maximum(1.0, jnp.max(jnp.abs(p), axis=-1))
    sign = jnp.where(dot(n, d) >= 0.0, 1.0, -1.0)
    return p + (sign * eps)[..., None] * n


# -- shading-frame trig (pbrt reflection.h inline helpers) ---------------
# all operate on directions in the local frame where n = (0,0,1)

def cos_theta(w):
    return w[..., 2]


def cos2_theta(w):
    return w[..., 2] * w[..., 2]


def abs_cos_theta(w):
    return jnp.abs(w[..., 2])


def sin2_theta(w):
    return jnp.maximum(0.0, 1.0 - cos2_theta(w))


def sin_theta(w):
    return jnp.sqrt(sin2_theta(w))


def tan_theta(w):
    return sin_theta(w) / jnp.where(jnp.abs(cos_theta(w)) < 1e-8, 1e-8, cos_theta(w))


def tan2_theta(w):
    c2 = cos2_theta(w)
    return sin2_theta(w) / jnp.maximum(c2, 1e-12)


def cos_phi(w):
    s = sin_theta(w)
    return jnp.where(s == 0.0, 1.0, jnp.clip(w[..., 0] / jnp.maximum(s, 1e-12), -1.0, 1.0))


def sin_phi(w):
    s = sin_theta(w)
    return jnp.where(s == 0.0, 0.0, jnp.clip(w[..., 1] / jnp.maximum(s, 1e-12), -1.0, 1.0))


def same_hemisphere(w, wp):
    return w[..., 2] * wp[..., 2] > 0.0
