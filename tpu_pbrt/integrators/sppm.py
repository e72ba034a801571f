"""SPPMIntegrator — stochastic progressive photon mapping, TPU-native.

Capability match for pbrt-v3 src/integrators/sppm.{h,cpp}
SPPMIntegrator::Render: per-iteration camera pass storing per-pixel
visible points, photon pass from Light::Sample_Le random walks, per-pixel
radius/flux updates (the Knaus-Zwicker style progressive shrink with
gamma = 2/3), and the final estimate
L = Ld/N_iter + tau / (N_iter * photonsPerIteration * pi * r^2).

TPU-first redesign of the two racy structures (SURVEY.md §5.2, §7 stage 8):
- pbrt's uniform hash grid of std::atomic linked lists (sppm.cpp grid
  build) becomes SORT-BY-CELL + searchsorted runs: photon deposits are
  sorted by integer cell id, each visible point scans the (bounded) runs
  of the up-to-8 cells overlapped by its radius-r bounding box, and the
  distance test decides membership exactly as in the reference. No
  atomics anywhere; the result is deterministic up to f32 addition order
  within a run (tested by photon-permutation invariance).
- pbrt's AtomicFloat Phi[3] accumulation becomes a dense masked
  sum over the scanned run slots.
- cross-device photon exchange (the fork's "global ray sort + photon
  atomics" axis): pixels AND photons shard over the mesh; each device
  traces its pixel shard's visible points and a disjoint global-id
  range of photons, then jax.lax.all_gather over ICI replicates the
  deposits so every device gathers its own visible points against the
  FULL photon set. Per-pixel state stays sharded; only the deposit
  exchange and the global max-radius (pmax) cross devices. The shard
  union reproduces the single-device photon set exactly, so a mesh
  render equals the single-device one up to f32 accumulation order
  (tested on a 4-device CPU mesh).


Capacity note: every cell run is scanned to EXHAUSTION — a while_loop
walks each run in `scancap`-photon chunks, so nothing is ever dropped
(pbrt's linked lists are unbounded and so, effectively, is this; the
chunk size only trades loop iterations against per-chunk width). The
`photons_dropped` stat is kept for API stability and is always 0.
"""

from __future__ import annotations

from typing import NamedTuple

import time

import jax
import jax.numpy as jnp
import numpy as np

from tpu_pbrt.cameras import generate_rays
from tpu_pbrt.core import bxdf
from tpu_pbrt.core import lights_dev as ld
from tpu_pbrt.core.sampling import hash_u32, sobol_2d, uniform_float
from tpu_pbrt.core.vecmath import (
    dot,
    normalize,
    offset_ray_origin,
    to_local,
    to_world,
)
from tpu_pbrt.integrators.common import (
    DIM_LENS,
    DIM_MIX,
    DIMS_PER_BOUNCE,
    RenderResult,
    WavefrontIntegrator,
    estimate_direct,
    make_interaction,
    scene_intersect,
)
from tpu_pbrt.parallel.mesh import vary

# sampler-dimension salt bases for the two SPPM streams
_SALT_CAM = 12001
_SALT_PHOTON = 24001

#: progressive radius shrink parameter (sppm.cpp gamma)
_GAMMA = 2.0 / 3.0


class _VisiblePoints(NamedTuple):
    """SoA per-pixel visible points for one iteration (sppm.h VisiblePoint)."""

    p: jnp.ndarray  # (P,3)
    wo: jnp.ndarray  # (P,3) world
    ns: jnp.ndarray  # (P,3) shading frame
    ss: jnp.ndarray
    ts: jnp.ndarray
    beta: jnp.ndarray  # (P,3)
    uv: jnp.ndarray  # (P,2) surface uv (texture evaluation at gather)
    mat: jnp.ndarray  # (P,) material id, -1 = no VP this iteration
    ld: jnp.ndarray  # (P,3) this iteration's direct/emitted radiance


class _SPPMState(NamedTuple):
    """Persistent per-pixel state across iterations (sppm.h SPPMPixel)."""

    r2: jnp.ndarray  # (P,) current search radius^2
    n: jnp.ndarray  # (P,) accumulated photon count (gamma-weighted)
    tau: jnp.ndarray  # (P,3) accumulated flux
    ld: jnp.ndarray  # (P,3) accumulated direct radiance
    dropped: jnp.ndarray  # () photons truncated by scan_cap (stat)


class SPPMIntegrator(WavefrontIntegrator):
    name = "sppm"
    rays_per_camera_ray = 3.0

    def __init__(self, params, scene, options):
        super().__init__(params, scene, options)
        self.max_depth = params.find_one_int("maxdepth", 5)
        self.n_iterations = params.find_one_int("numiterations", 64)
        self.photons_per_iter = params.find_one_int("photonsperiteration", -1)
        self.initial_radius = params.find_one_float("radius", 1.0)
        #: photons per gather chunk (see capacity note above — a width/
        #: iterations tradeoff, not a truncation bound)
        self.scan_cap = params.find_one_int("scancap", 32)
        from tpu_pbrt.utils.error import Warning as _W

        if scene.has_null_materials:
            _W("sppm: null-interface materials are traversed as opaque")

    # ------------------------------------------------------------------
    # camera pass: one VP per pixel (sppm.cpp "Generate SPPM visible points")
    # ------------------------------------------------------------------
    def _camera_pass(self, dev, px, py, it_idx):
        scene = self.scene
        cam = scene.camera
        shape = px.shape
        s = jnp.full(shape, it_idx, jnp.int32)
        sx_scr = hash_u32(px, py, 0x31)
        sy_scr = hash_u32(px, py, 0x42)
        fx, fy = sobol_2d(s, sx_scr, sy_scr)
        p_film = jnp.stack(
            [px.astype(jnp.float32) + fx, py.astype(jnp.float32) + fy], -1
        )
        u_lens = jnp.stack(
            [
                uniform_float(px, py, s, _SALT_CAM + DIM_LENS),
                uniform_float(px, py, s, _SALT_CAM + DIM_LENS + 1),
            ],
            -1,
        )
        o, d, wt = generate_rays(cam, p_film, u_lens)
        beta = jnp.broadcast_to(wt[..., None], shape + (3,)).astype(jnp.float32)

        ld_acc = jnp.zeros(shape + (3,), jnp.float32)
        vp_p = jnp.zeros(shape + (3,), jnp.float32)
        vp_wo = jnp.zeros(shape + (3,), jnp.float32)
        vp_ns = jnp.zeros(shape + (3,), jnp.float32)
        vp_ss = jnp.zeros(shape + (3,), jnp.float32)
        vp_ts = jnp.zeros(shape + (3,), jnp.float32)
        vp_beta = jnp.zeros(shape + (3,), jnp.float32)
        vp_uv = jnp.zeros(shape + (2,), jnp.float32)
        vp_mat = jnp.full(shape, -1, jnp.int32)
        alive = jnp.ones(shape, bool)
        specular = jnp.ones(shape, bool)  # first hit counts as "specular"
        nrays = jnp.zeros((), jnp.int32)

        # one fori_loop iteration per depth: bsdf_sample/estimate_direct
        # instantiate ONCE (a Python depth loop re-instantiates them per
        # depth and XLA's compile time is superlinear in module size —
        # measured: the unrolled md=3 camera pass alone took >10 min to
        # compile on CPU, the rolled one seconds)
        from tpu_pbrt.integrators.common import Interaction

        def body(depth, carry):
            (o, d, beta, alive, specular, ld_acc, vp_p, vp_wo, vp_ns, vp_ss,
             vp_ts, vp_beta, vp_uv, vp_mat, nrays) = carry
            salt = _SALT_CAM + depth * DIMS_PER_BOUNCE
            t_max = jnp.where(alive, jnp.inf, -1.0)
            hit = scene_intersect(dev, o, d, t_max)
            nrays = nrays + jnp.sum(alive.astype(jnp.int32))
            it = make_interaction(dev, hit, o, d)
            found = alive & it.valid
            # escaped rays: env radiance (specular/first only, as in path)
            if "envmap" in dev:
                miss = alive & (hit.prim < 0) & specular
                ld_acc = ld_acc + jnp.where(
                    miss[..., None], beta * ld.env_lookup(dev, d), 0.0
                )
            # emitted at the hit (specular chains / first hit)
            le = ld.emitted_radiance(dev, jnp.where(found, it.light, -1), it.wo, it.ng)
            ld_acc = ld_acc + jnp.where(
                (found & specular)[..., None], beta * le, 0.0
            )
            mp = self.mat_at(
                dev, it, u_mix=uniform_float(px, py, s, salt + DIM_MIX)
            )
            # direct lighting at every real vertex (sppm.cpp accumulates
            # UniformSampleOneLight into pixel.Ld)
            it_masked = Interaction(
                it.p, it.ng, it.ns, it.ss, it.ts, it.uv, it.mat, it.light,
                it.wo, found,
            )
            ld_acc = ld_acc + beta * estimate_direct(
                dev,
                self.light_distr,
                it_masked,
                mp,
                px,
                py,
                s,
                depth,
                salt_extra=_SALT_CAM + 500,
                vis_segments=self.vis_segments,
                # the sample index here is it_idx in [0, n_iterations), NOT
                # a [0, spp) sampler index: the stratification domain must
                # cover the iteration count or later iterations replay the
                # same permuted NEE samples and direct light never converges
                sampler=(self.skind, self.n_iterations),
            )
            nrays = nrays + 2 * jnp.sum(found.astype(jnp.int32))
            has_diffuse, has_glossy, is_spec = bxdf._lobe_flags(mp)
            store = found & (has_diffuse | (has_glossy & (depth == self.max_depth - 1)))
            vp_p = jnp.where(store[..., None], it.p, vp_p)
            vp_wo = jnp.where(store[..., None], it.wo, vp_wo)
            vp_ns = jnp.where(store[..., None], it.ns, vp_ns)
            vp_ss = jnp.where(store[..., None], it.ss, vp_ss)
            vp_ts = jnp.where(store[..., None], it.ts, vp_ts)
            vp_beta = jnp.where(store[..., None], beta, vp_beta)
            vp_uv = jnp.where(store[..., None], it.uv, vp_uv)
            vp_mat = jnp.where(store, it.mat, vp_mat)
            alive = found & ~store
            # continue by BSDF sampling (specular/glossy chains); the last
            # depth's continuation is dead (alive is masked out below)
            wo_l = to_local(it.wo, it.ss, it.ts, it.ns)
            bs = bxdf.bsdf_sample(
                mp,
                wo_l,
                uniform_float(px, py, s, salt + 7),
                uniform_float(px, py, s, salt + 8),
                uniform_float(px, py, s, salt + 9),
            )
            wi_w = normalize(to_world(bs.wi, it.ss, it.ts, it.ns))
            cont = alive & (bs.pdf > 0.0) & (jnp.max(bs.f, axis=-1) > 0.0)
            thr = bs.f * (jnp.abs(dot(wi_w, it.ns)) / jnp.maximum(bs.pdf, 1e-20))[..., None]
            beta = jnp.where(cont[..., None], beta * thr, beta)
            specular = bs.is_specular
            o = jnp.where(cont[..., None], offset_ray_origin(it.p, it.ng, wi_w), o)
            d = jnp.where(cont[..., None], wi_w, d)
            alive = cont & (depth < self.max_depth - 1)
            return (o, d, beta, alive, specular, ld_acc, vp_p, vp_wo, vp_ns,
                    vp_ss, vp_ts, vp_beta, vp_uv, vp_mat, nrays)

        carry = (o, d, beta, alive, specular, ld_acc, vp_p, vp_wo, vp_ns,
                 vp_ss, vp_ts, vp_beta, vp_uv, vp_mat, nrays)
        (o, d, beta, alive, specular, ld_acc, vp_p, vp_wo, vp_ns, vp_ss,
         vp_ts, vp_beta, vp_uv, vp_mat, nrays) = jax.lax.fori_loop(
            0, self.max_depth, body, vary(carry)
        )
        return (
            _VisiblePoints(
                vp_p, vp_wo, vp_ns, vp_ss, vp_ts, vp_beta, vp_uv, vp_mat, ld_acc
            ),
            nrays,
        )

    # ------------------------------------------------------------------
    # photon pass (sppm.cpp "Trace photons and accumulate contributions")
    # ------------------------------------------------------------------
    def _photon_pass(self, dev, n_photons, it_idx, pid0=0):
        """Trace n_photons light subpaths; return deposit SoA of shape
        (n_photons, max_depth): position, incident direction (the photon's
        travel direction), beta, valid. Deposits skip depth 0 (direct
        lighting is the camera pass's NEE, as in the reference). pid0
        offsets the photon RNG stream ids — the mesh path gives each
        device a disjoint global id range so the union of shards is
        EXACTLY the single-device photon set."""
        pid = pid0 + jnp.arange(n_photons, dtype=jnp.int32)
        py = jnp.full((n_photons,), 0x5995, jnp.int32) + it_idx
        s = jnp.full((n_photons,), it_idx, jnp.int32)

        def u(salt):
            return uniform_float(pid, py, s, _SALT_PHOTON + salt)

        les = ld.sample_le(dev, self.scene.light_distr, u(0), u(1), u(2), u(3), u(4))
        cos0 = jnp.where(les.is_delta, 1.0, jnp.abs(dot(les.n, les.d)))
        denom = jnp.maximum(les.pmf * les.pdf_pos * les.pdf_dir, 1e-20)
        beta = les.le * (cos0 / denom)[..., None]
        alive = les.supported & (jnp.max(beta, axis=-1) > 0.0)
        o = offset_ray_origin(les.p, les.n, les.d)
        o = jnp.where(les.is_delta[..., None], les.p, o)
        d = les.d

        D = self.max_depth
        dep_p = jnp.zeros((n_photons, D, 3), jnp.float32)
        dep_d = jnp.zeros((n_photons, D, 3), jnp.float32)
        dep_beta = jnp.zeros((n_photons, D, 3), jnp.float32)
        dep_valid = jnp.zeros((n_photons, D), bool)
        nrays = jnp.zeros((), jnp.int32)

        # rolled loop (fori_loop) for the same compile-size reason as the
        # camera pass: one bsdf_sample instantiation for all depths
        def body(depth, carry):
            o, d, beta, alive, dep_p, dep_d, dep_beta, dep_valid, nrays = carry
            salt = 100 + depth * DIMS_PER_BOUNCE
            t_max = jnp.where(alive, jnp.inf, -1.0)
            hit = scene_intersect(dev, o, d, t_max)
            nrays = nrays + jnp.sum(alive.astype(jnp.int32))
            it = make_interaction(dev, hit, o, d)
            found = alive & it.valid
            dep_found = found & (depth > 0)  # depth 0 = direct (NEE covers it)
            dep_p = jax.lax.dynamic_update_index_in_dim(
                dep_p, jnp.where(dep_found[..., None], it.p, 0.0), depth, 1
            )
            dep_d = jax.lax.dynamic_update_index_in_dim(dep_d, d, depth, 1)
            dep_beta = jax.lax.dynamic_update_index_in_dim(
                dep_beta, jnp.where(dep_found[..., None], beta, 0.0), depth, 1
            )
            dep_valid = jax.lax.dynamic_update_index_in_dim(
                dep_valid, dep_found, depth, 1
            )
            mp = self.mat_at(dev, it, u_mix=u(salt + DIM_MIX))
            wo_l = to_local(it.wo, it.ss, it.ts, it.ns)
            bs = bxdf.bsdf_sample(mp, wo_l, u(salt + 7), u(salt + 8), u(salt + 9))
            wi_w = normalize(to_world(bs.wi, it.ss, it.ts, it.ns))
            cont = found & (bs.pdf > 0.0) & (jnp.max(bs.f, axis=-1) > 0.0)
            # importance transport: shading-normal correction (bdpt.cpp
            # CorrectShadingNormals)
            num = jnp.abs(dot(it.wo, it.ns)) * jnp.abs(dot(wi_w, it.ng))
            den = jnp.maximum(jnp.abs(dot(it.wo, it.ng)) * jnp.abs(dot(wi_w, it.ns)), 1e-9)
            thr = bs.f * (jnp.abs(dot(wi_w, it.ns)) / jnp.maximum(bs.pdf, 1e-20))[..., None]
            beta_new = beta * thr * (num / den)[..., None]
            # Russian roulette on the throughput ratio (sppm.cpp photon RR)
            by = jnp.max(beta, axis=-1)
            bny = jnp.max(beta_new, axis=-1)
            q = jnp.maximum(0.0, 1.0 - bny / jnp.maximum(by, 1e-20))
            u_rr = u(salt + 10)
            survive = u_rr >= q
            beta = jnp.where(
                (cont & survive)[..., None],
                beta_new / jnp.maximum(1.0 - q, 1e-6)[..., None],
                beta_new,
            )
            alive = cont & survive
            o = jnp.where(alive[..., None], offset_ray_origin(it.p, it.ng, wi_w), o)
            d = jnp.where(alive[..., None], wi_w, d)
            return o, d, beta, alive, dep_p, dep_d, dep_beta, dep_valid, nrays

        carry = (o, d, beta, alive, dep_p, dep_d, dep_beta, dep_valid, nrays)
        _, _, _, _, dep_p, dep_d, dep_beta, dep_valid, nrays = jax.lax.fori_loop(
            0, D, body, vary(carry)
        )
        return (
            dep_p.reshape(-1, 3),
            dep_d.reshape(-1, 3),
            dep_beta.reshape(-1, 3),
            dep_valid.reshape(-1),
            nrays,
        )

    # ------------------------------------------------------------------
    # gather: sort deposits by cell, VPs scan their 8 overlapped cells
    # ------------------------------------------------------------------
    def _gather(self, dev, vps: _VisiblePoints, dep_p, dep_d, dep_beta,
                dep_valid, r2, lo, cs, gres):
        """Returns (phi (P,3), m (P,), dropped ()). lo/cs/gres define the
        grid: cell = floor((p - lo)/cs), linear id = x + gx*(y + gy*z)."""
        K = self.scan_cap
        P = vps.p.shape[0]
        n_dep = dep_p.shape[0]
        gx, gy, gz = gres

        def cell_of(p):
            c = jnp.floor((p - lo) / cs).astype(jnp.int32)
            c = jnp.clip(c, 0, jnp.asarray([gx - 1, gy - 1, gz - 1], jnp.int32))
            return c[..., 0] + gx * (c[..., 1] + gy * c[..., 2])

        n_cells = gx * gy * gz
        dcell = jnp.where(dep_valid, cell_of(dep_p), n_cells)
        dcell_s, order = jax.lax.sort(
            [dcell, jax.lax.iota(jnp.int32, n_dep)], num_keys=1
        )
        dp_s = dep_p[order]
        dd_s = dep_d[order]
        db_s = dep_beta[order]

        has_vp = vps.mat >= 0
        r = jnp.sqrt(r2)
        base = jnp.floor((vps.p - lo - r[..., None]) / cs).astype(jnp.int32)
        from tpu_pbrt.integrators.common import textured_mat

        mp_vp = textured_mat(
            dev, jnp.maximum(vps.mat, 0), vps.uv, vps.p, self.tex_eval, self.tex_used
        )
        wo_l = to_local(vps.wo, vps.ss, vps.ts, vps.ns)

        # collect the 8 overlapped cells' run windows first (cheap index
        # math): starts/ends (P, 8)
        starts = []
        ends = []
        for ox in (0, 1):
            for oy in (0, 1):
                for oz in (0, 1):
                    c = base + jnp.asarray([ox, oy, oz], jnp.int32)
                    inb = (
                        (c[..., 0] >= 0) & (c[..., 0] < gx)
                        & (c[..., 1] >= 0) & (c[..., 1] < gy)
                        & (c[..., 2] >= 0) & (c[..., 2] < gz)
                    )
                    use = has_vp & inb
                    cid = jnp.where(
                        use, c[..., 0] + gx * (c[..., 1] + gy * c[..., 2]), n_cells
                    )
                    st = jnp.searchsorted(dcell_s, cid, side="left").astype(jnp.int32)
                    en = jnp.searchsorted(dcell_s, cid, side="right").astype(jnp.int32)
                    # lanes with no VP / out-of-grid cell scan nothing (the
                    # n_cells sentinel's run is the invalid-deposit tail)
                    starts.append(st)
                    ends.append(jnp.where(use, en, st))
        start8 = jnp.stack(starts, axis=1)  # (P, 8)
        end8 = jnp.stack(ends, axis=1)

        # scan each run in K-photon chunks inside ONE while_loop (a single
        # bsdf_eval instantiation, like the fori-rolled passes): every run
        # is scanned to EXHAUSTION — pbrt's unbounded linked lists drop
        # nothing, and neither does this. The loop runs until the wave's
        # longest remaining run is done; early iterations (radius spanning
        # few coarse cells) simply take more chunks.
        mp_b = jax.tree.map(
            lambda a: a[:, None] if a.ndim == 1 else a[:, None, :], mp_vp
        )
        wo_b = wo_l[:, None, :]
        koff = jnp.arange(K, dtype=jnp.int32)

        def cond(carry):
            j, phi, m = carry
            return jnp.any(start8 + j * K < end8)

        def body(carry):
            j, phi, m = carry
            # (P, 8, K) slots for this chunk of every cell's run
            slot = start8[..., None] + j * K + koff[None, None, :]
            ok = slot < end8[..., None]
            slot = jnp.minimum(slot, n_dep - 1).reshape(P, 8 * K)
            ok = ok.reshape(P, 8 * K)
            ppos = dp_s[slot]  # (P,8K,3)
            diff = ppos - vps.p[:, None, :]
            d2 = jnp.sum(diff * diff, axis=-1)
            within = ok & (d2 <= r2[:, None])
            wi_w = -dd_s[slot]
            wi_l = to_local(
                wi_w, vps.ss[:, None, :], vps.ts[:, None, :], vps.ns[:, None, :]
            )
            f, _ = bxdf.bsdf_eval(mp_b, wo_b, wi_l)
            contrib = jnp.where(within[..., None], f * db_s[slot], 0.0)
            return (
                j + 1,
                phi + jnp.sum(contrib, axis=1),
                m + jnp.sum(within, axis=1).astype(jnp.float32),
            )

        _, phi, m = jax.lax.while_loop(
            cond, body,
            vary((
                jnp.int32(0), jnp.zeros((P, 3), jnp.float32),
                jnp.zeros((P,), jnp.float32),
            )),
        )
        return phi, m, jnp.zeros((), jnp.int32)

    # ------------------------------------------------------------------
    def _mesh_iteration(self, dev, mesh, state, px, py, P, n_photons):
        """Build the sharded per-iteration step (see module doc): pixels
        and photons shard over the mesh axis; photon deposits all_gather
        over ICI; per-pixel state stays sharded. Returns (iteration_fn,
        possibly padded state, total photon count)."""
        from functools import partial

        from tpu_pbrt.parallel.mesh import TILE_AXIS, shard_map
        from jax.sharding import NamedSharding, PartitionSpec as PS

        n_dev = int(mesh.devices.size)
        pad = (-P) % n_dev
        if pad:
            # padded lanes duplicate pixel 0; their state rows are
            # dropped at develop time (render slices [:P])
            px = jnp.concatenate([px, jnp.repeat(px[:1], pad)])
            py = jnp.concatenate([py, jnp.repeat(py[:1], pad)])
            state = _SPPMState(
                r2=jnp.concatenate([state.r2, jnp.repeat(state.r2[:1], pad)]),
                n=jnp.concatenate([state.n, jnp.zeros((pad,), jnp.float32)]),
                tau=jnp.concatenate([state.tau, jnp.zeros((pad, 3), jnp.float32)]),
                ld=jnp.concatenate([state.ld, jnp.zeros((pad, 3), jnp.float32)]),
                dropped=state.dropped,
            )
        npd = -(-n_photons // n_dev)  # photons per device
        n_total = npd * n_dev

        shard = NamedSharding(mesh, PS(TILE_AXIS))
        state = _SPPMState(
            r2=jax.device_put(state.r2, shard),
            n=jax.device_put(state.n, shard),
            tau=jax.device_put(state.tau, shard),
            ld=jax.device_put(state.ld, shard),
            dropped=state.dropped,
        )
        px = jax.device_put(px, shard)
        py = jax.device_put(py, shard)

        # THREE separate shard_map jits, mirroring the single-device
        # cam/photon/gather split: XLA:CPU compile time is superlinear in
        # module size and one fused sharded module takes tens of minutes
        # to build (the split compiles like the single-device modules)
        sm = partial(shard_map, mesh=mesh)

        @jax.jit
        @partial(
            sm,
            in_specs=(PS(), PS(TILE_AXIS), PS(TILE_AXIS), PS()),
            out_specs=(PS(TILE_AXIS), PS()),
        )
        def cam_shard(dev_, px_s, py_s, it_idx):
            vps, nrays = self._camera_pass(dev_, px_s, py_s, it_idx)
            return vps, jax.lax.psum(nrays, TILE_AXIS)

        @jax.jit
        @partial(sm, in_specs=(PS(), PS()), out_specs=(PS(TILE_AXIS), PS()))
        def photon_shard(dev_, it_idx):
            didx = jax.lax.axis_index(TILE_AXIS)
            dep_p, dep_d, dep_beta, dep_valid, nrays = self._photon_pass(
                dev_, npd, it_idx, pid0=didx * npd
            )
            return (dep_p, dep_d, dep_beta, dep_valid), jax.lax.psum(
                nrays, TILE_AXIS
            )

        @jax.jit
        @partial(
            sm,
            in_specs=(
                PS(),
                (PS(TILE_AXIS),) * 4,
                PS(TILE_AXIS),
                (PS(TILE_AXIS),) * 4,
            ),
            out_specs=((PS(TILE_AXIS),) * 4, PS()),
        )
        def gather_shard(dev_, state_tup, vps, deps):
            r2_s, n_s, tau_s, ld_s = state_tup
            # ICI photon exchange: every device sees the full deposit set
            dep_p, dep_d, dep_beta, dep_valid = (
                jax.lax.all_gather(x, TILE_AXIS, tiled=True) for x in deps
            )
            # grid cell size from the GLOBAL max radius so every shard
            # bins photons identically
            r_max = jax.lax.pmax(jnp.sqrt(jnp.max(r2_s)), TILE_AXIS)
            verts_lo = dev_["world_center"] - dev_["world_radius"]
            verts_hi = dev_["world_center"] + dev_["world_radius"]
            glo = verts_lo - r_max
            ext = (verts_hi + r_max) - glo
            cs = jnp.maximum(2.0 * r_max, jnp.max(ext) / 64.0)
            gres = (64, 64, 64)
            phi, m, dropped = self._gather(
                dev_, vps, dep_p, dep_d, dep_beta, dep_valid, r2_s, glo,
                cs, gres,
            )
            has = m > 0.0
            n_new = n_s + _GAMMA * m
            denom = jnp.maximum(n_s + m, 1e-20)
            r2_new = r2_s * n_new / denom
            tau_new = (tau_s + vps.beta * phi) * (
                r2_new / jnp.maximum(r2_s, 1e-30)
            )[..., None]
            out = (
                jnp.where(has, r2_new, r2_s),
                jnp.where(has, n_new, n_s),
                jnp.where(has[..., None], tau_new, tau_s),
                ld_s + vps.ld,
            )
            return out, jax.lax.psum(dropped, TILE_AXIS)

        def iteration(state: _SPPMState, it_idx):
            vps, nr_c = cam_shard(dev, px, py, it_idx)
            deps, nr_p = photon_shard(dev, it_idx)
            tup = (state.r2, state.n, state.tau, state.ld)
            (r2, n, tau, ld_), dropped = gather_shard(dev, tup, vps, deps)
            return (
                _SPPMState(r2=r2, n=n, tau=tau, ld=ld_,
                           dropped=state.dropped + dropped),
                nr_c + nr_p,
            )

        return iteration, state, n_total

    def render(self, scene=None, mesh=None, max_seconds: float = 0.0, **kw) -> RenderResult:
        scene = scene or self.scene
        dev = scene.dev
        film = scene.film
        x0, x1, y0, y1 = film.sample_bounds()
        w = x1 - x0
        h = y1 - y0
        P = w * h
        n_photons = self.photons_per_iter if self.photons_per_iter > 0 else P
        n_iter = self.n_iterations

        pix = jnp.arange(P, dtype=jnp.int32)
        px = x0 + pix % w
        py = y0 + pix // w

        # initial radius: pbrt's initialSearchRadius param; scale-free
        # default = 2 x pixel footprint estimate from the scene diagonal
        verts = np.asarray(dev["tri_verts"]).reshape(-1, 3)
        s_lo = verts.min(0)
        s_hi = verts.max(0)
        diag = float(np.linalg.norm(s_hi - s_lo))
        r0 = self.initial_radius
        if r0 <= 0.0:
            r0 = 2.0 * diag / max(w, h)

        state = _SPPMState(
            r2=jnp.full((P,), r0 * r0, jnp.float32),
            n=jnp.zeros((P,), jnp.float32),
            tau=jnp.zeros((P, 3), jnp.float32),
            ld=jnp.zeros((P, 3), jnp.float32),
            dropped=jnp.zeros((), jnp.int32),
        )

        # three separate jits instead of one fused `iteration`: XLA:CPU
        # compile time is strongly superlinear in module size (LLVM on the
        # giant fused loops), so splitting the phases compiles ~an order of
        # magnitude faster for identical runtime work
        cam_j = jax.jit(self._camera_pass)
        ph_j = jax.jit(self._photon_pass, static_argnums=(1,))

        @jax.jit
        def gather_update(state: _SPPMState, vps, dep_p, dep_d, dep_beta, dep_valid):
            # grid for THIS iteration: cell size from the current max radius
            r_max = jnp.sqrt(jnp.max(state.r2))
            glo = jnp.asarray(s_lo, jnp.float32) - r_max
            ghi = jnp.asarray(s_hi, jnp.float32) + r_max
            ext = ghi - glo
            # static grid resolution bound (64^3 < 2^31 linear ids); the
            # dynamic cell size still adapts to the shrinking radius
            cs = jnp.maximum(2.0 * r_max, jnp.max(ext) / 64.0)
            gres = (64, 64, 64)
            phi, m, dropped = self._gather(
                dev, vps, dep_p, dep_d, dep_beta, dep_valid, state.r2, glo, cs, gres
            )
            # progressive update (sppm.cpp "Update pixel values from this
            # pass's photons")
            has = m > 0.0
            n_new = state.n + _GAMMA * m
            denom = jnp.maximum(state.n + m, 1e-20)
            r2_new = state.r2 * n_new / denom
            tau_new = (state.tau + vps.beta * phi) * (r2_new / jnp.maximum(state.r2, 1e-30))[..., None]
            return _SPPMState(
                r2=jnp.where(has, r2_new, state.r2),
                n=jnp.where(has, n_new, state.n),
                tau=jnp.where(has[..., None], tau_new, state.tau),
                ld=state.ld + vps.ld,
                dropped=state.dropped + dropped,
            )

        def iteration(state: _SPPMState, it_idx):
            vps, nrays_c = cam_j(dev, px, py, it_idx)
            dep_p, dep_d, dep_beta, dep_valid, nrays_p = ph_j(dev, n_photons, it_idx)
            state = gather_update(state, vps, dep_p, dep_d, dep_beta, dep_valid)
            return state, nrays_c + nrays_p

        if mesh is not None and mesh.devices.size > 1:
            iteration, state, n_photons = self._mesh_iteration(
                dev, mesh, state, px, py, P, n_photons
            )

        t0 = time.time()
        rays = 0
        iters_done = 0
        from tpu_pbrt.utils.stats import STATS, ProgressReporter

        progress = ProgressReporter(
            n_iter, "SPPM", quiet=bool(getattr(self.options, "quiet", False))
        )
        with STATS.phase("Integrator/SPPM render"):
            for i in range(n_iter):
                state, nr = iteration(state, jnp.int32(i))
                rays += int(nr)
                iters_done = i + 1
                progress.update()
                if max_seconds > 0 and time.time() - t0 > max_seconds:
                    break
        progress.done()
        secs = time.time() - t0

        STATS.counter("SPPM/Photons dropped (scan cap)", int(state.dropped))
        STATS.counter("Integrator/Rays traced", rays)

        ni = max(iters_done, 1)
        ld_img = np.asarray(state.ld)[:P].reshape(h, w, 3) / ni
        tau = np.asarray(state.tau)[:P].reshape(h, w, 3)
        r2 = np.asarray(state.r2)[:P].reshape(h, w, 1)
        img = ld_img + tau / (ni * n_photons * np.pi * r2)
        img = np.ascontiguousarray(img, np.float32)
        if film.filename:
            try:
                from tpu_pbrt.utils.imageio import write_image as _wi

                _wi(film.filename, img)
            except Exception as e:  # noqa: BLE001
                from tpu_pbrt.utils.error import Warning as _W

                _W(f"could not write image {film.filename}: {e}")
        return RenderResult(
            image=img,
            film_state=None,
            seconds=secs,
            rays_traced=rays,
            mray_per_sec=rays / max(secs, 1e-9) / 1e6,
            spp=ni,
            completed_fraction=iters_done / max(n_iter, 1),
            stats={"photons_dropped": int(state.dropped)},
        )
