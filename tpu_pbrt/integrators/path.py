"""PathIntegrator — the north-star wavefront bounce loop.

Capability match for pbrt-v3 src/integrators/path.{h,cpp} PathIntegrator::Li
(SURVEY.md §3.3): iterative bounce loop with emission on miss/first-hit,
NEE with MIS, BSDF importance sampling for the continuation, beta updates,
and Russian roulette after depth 3 with the eta^2 radiance correction.

TPU-first redesign (SURVEY.md §7): the per-ray recursion becomes a
wavefront — the whole ray batch advances one bounce per `lax.while_loop`
iteration under a live mask, with all control flow as masked selects. One
compiled bounce body serves every depth (compile time and program size are
constant in maxdepth — a Python-unrolled loop at production depth
overflowed the XLA program budget), and the loop exits as soon as every
lane is dead. The MIS bookkeeping uses the forward formulation (pbrt-v4
style): instead of EstimateDirect's extra BSDF-MIS shadow ray per bounce,
the continuation ray itself carries the BSDF pdf, and emitters hit by it
are weighted by power_heuristic(bsdf_pdf, light_pdf). Identical
expectation to the reference estimator, one ray cheaper per bounce.

Persistent wavefront (ISSUE 1 tentpole): the fixed-batch loop above leaves
most lanes dead after the first bounces (miss / RR) while every remaining
wave still pays full-width shading, NEE and sampling for them. The default
render path is therefore the Laine/Karras/Aila-style wavefront with
REGENERATION IN PLACE (`pool_chunk`): a resident pool of path slots is
advanced one bounce per wave; terminated lanes scatter their L into the
film and are refilled where they lie with fresh camera rays drained from a
per-chunk work counter (the k-th free slot in lane order takes the k-th
next work item: a prefix count of the free mask; no lane is moved, since
everything downstream masks or sorts for itself), so every trace and
shading wave runs near 100% occupancy. Because every sampler
dimension is a pure function of (px, py, s, dimension), a regenerated lane
reproduces exactly the sample stream the fixed-batch loop would have drawn
— the estimator (and the image, up to float accumulation order) is
identical. That holds for every sampler: a dimension salt may be a
per-lane array (halton picks each lane's pair of prime bases by a select,
core/sampling.py::_halton_pair). `TPU_PBRT_REGEN=0` falls back to the
fixed-batch loop, which also remains the path for scenes the pool does not
support (null-interface materials, multi-segment Tr).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from tpu_pbrt.core import bxdf
from tpu_pbrt.core import lights_dev as ld
from tpu_pbrt.core.film import FilmState
from tpu_pbrt.core.sampling import power_heuristic, uniform_float
from tpu_pbrt.core.vecmath import dot, normalize, offset_ray_origin, to_local, to_world
from tpu_pbrt.integrators.common import (
    scene_intersect,
    scene_intersect_fused,
    scene_intersect_p,
    unoccluded_tr,
    DIM_BSDF_LOBE,
    DIM_BSDF_UV,
    DIM_LIGHT_PICK,
    DIM_LIGHT_UV,
    DIM_MIX,
    DIM_RR,
    DIM_TIME,
    DIMS_PER_BOUNCE,
    WavefrontIntegrator,
    make_interaction,
    texture_footprint,
)
from tpu_pbrt.obs import phases as ph
from tpu_pbrt.parallel.mesh import vary
from tpu_pbrt.scene.compiler import MAT_NONE

PASSTHROUGH_MARGIN = 4

#: 2D sampler draws of one `_bounce_wave`: DIM_LIGHT_UV and DIM_BSDF_UV
#: (what the counter `halton_pairs` counts a live lane for)
PAIRS_PER_BOUNCE = 2

#: the deposit packs (not_done << 30) | lane into one int32 sort key
_POOL_LANE_BITS = 30


def _free_slot_work(has_work, cursor, n_work):
    """Which work item each free pool slot takes this wave, found where
    the slot lies: the k-th free slot in lane order takes item
    `cursor + k`, k its rank among the free slots (an exclusive prefix
    count of `~has_work`), so no lane has to move to make room.

    Returns (widx, can, consumed): the per-lane work index (meaningful
    where `can`), the lanes that take one (free and `widx < n_work`), and
    how far the cursor advances — every item handed out, which also
    consumes work items whose pixel falls past the frame (the final
    chunk's tail; the fixed-batch loop likewise masks them out)."""
    free = ~has_work
    upto = jnp.cumsum(free, dtype=jnp.int32)  # free slots up to and with this lane
    widx = cursor + upto - free.astype(jnp.int32)
    can = free & (widx < n_work)
    consumed = jnp.clip(n_work - cursor, 0, upto[-1])
    return widx, can, consumed


class LaneSt(NamedTuple):
    """Per-lane path state — everything a path carries between bounces.
    Shared by the fixed-batch loop (all lanes in lockstep) and the
    persistent pool (lanes at mixed depths)."""

    o: jnp.ndarray
    d: jnp.ndarray
    L: jnp.ndarray
    beta: jnp.ndarray
    alive: jnp.ndarray
    depth: jnp.ndarray  # per-lane real (non-null) bounces taken; also the
    # lane's sampler-dimension salt base in pool mode
    prev_pdf: jnp.ndarray
    specular: jnp.ndarray
    eta_scale: jnp.ndarray
    prev_p: jnp.ndarray
    sh_o: jnp.ndarray  # pending shadow ray (fused mode)
    sh_d: jnp.ndarray
    sh_dist: jnp.ndarray  # < 0: no pending shadow
    ld_pend: jnp.ndarray  # beta-weighted NEE contribution awaiting
    # the pending shadow's visibility


def fresh_lanes(o, d) -> LaneSt:
    """Camera-ray lane state: the MIS state treats the camera 'bounce' as
    specular."""
    shape = o.shape[:-1]
    return LaneSt(
        o=o,
        d=d,
        L=jnp.zeros(shape + (3,), jnp.float32),
        beta=jnp.ones(shape + (3,), jnp.float32),
        alive=jnp.ones(shape, bool),
        depth=jnp.zeros(shape, jnp.int32),
        prev_pdf=jnp.zeros(shape, jnp.float32),
        specular=jnp.ones(shape, bool),
        eta_scale=jnp.ones(shape, jnp.float32),
        prev_p=o,
        sh_o=o,
        sh_d=d,
        sh_dist=jnp.full(shape, -1.0, jnp.float32),
        ld_pend=jnp.zeros(shape + (3,), jnp.float32),
    )


class PathIntegrator(WavefrontIntegrator):
    name = "path"

    def __init__(self, params, scene, options):
        super().__init__(params, scene, options)
        self.max_depth = params.find_one_int("maxdepth", 5)
        self.rr_threshold = params.find_one_float("rrthreshold", 1.0)
        # null-BSDF (interface/container) surfaces: pbrt spawns through them
        # without counting a bounce (path.cpp bounces--). The wavefront
        # equivalent is extra loop iterations + a per-lane real-bounce
        # counter; scenes without null materials pay nothing (ADVICE r1).
        self.margin = PASSTHROUGH_MARGIN if scene.has_null_materials else 0

    # -- regeneration support gate ----------------------------------------
    def _regen_enabled(self) -> bool:
        """Regeneration in place is ON by default for the path
        integrator wherever the pool's precondition holds: the fused 2R
        wave layout (single-segment visibility, no null passthrough).
        Every sampler's dimension salts work per lane."""
        from tpu_pbrt.config import cfg

        if not cfg.regen:
            return False
        return self.vis_segments == 1 and self.margin == 0

    # -- one wavefront step ------------------------------------------------
    def _bounce_wave(
        self, dev, px, py, s, salt, ray_time, st: LaneSt, nrays,
        *, fused: bool, scalar_bounce=None, ctr=None,
    ):
        """Advance every lane one bounce: trace (fused continuation +
        pending-shadow 2R wave when `fused`), settle the previous bounce's
        NEE, add emission with forward MIS, sample NEE + the BSDF
        continuation, run the BSSRDF probe wave if compiled in, and apply
        Russian roulette.

        `salt` is the sampler-dimension base — the scalar loop iteration *
        DIMS_PER_BOUNCE in fixed-batch mode, the per-lane depth *
        DIMS_PER_BOUNCE in pool mode (identical values for any live lane,
        so both modes draw the same streams). `scalar_bounce` enables the
        lax.cond skip of the camera-footprint block when the whole wave
        shares one bounce index; pool mode (None) masks per-lane instead.
        `ctr` is the optional telemetry counter block (obs/counters.py):
        this wave's ray count and occupancy-histogram bin are folded in
        here, structural drain counters in the pool body. Returns
        (LaneSt, nrays + this wave's per-lane traced-ray counts, ctr).
        """
        shape = st.o.shape[:-1]
        nrays_in = nrays  # telemetry: the wave's ray delta (ctr below)
        o, d, L, beta, alive = st.o, st.d, st.L, st.beta, st.alive
        depth, prev_pdf, specular = st.depth, st.prev_pdf, st.specular
        eta_scale, prev_p = st.eta_scale, st.prev_p

        # dead lanes traverse with t_max < 0: the root slab test fails
        # immediately, so they cost one loop iteration, not a walk.
        # "fused" here and below names the 2R camera+shadow wave only
        t_max = jnp.where(alive, jnp.inf, -1.0)
        work = None  # the 2R wave's tracer work counts (ctr below)
        if fused:
            R = o.shape[0]
            hit, sh_prim, work = scene_intersect_fused(
                dev,
                jnp.concatenate([o, st.sh_o]),
                jnp.concatenate([d, st.sh_d]),
                jnp.concatenate([t_max, st.sh_dist]),
                n_cam=R,
                # shadow rays inherit their camera sample's time
                time=None if ray_time is None
                else jnp.concatenate([ray_time, ray_time]),
            )
            # settle the previous bounce's NEE with its visibility
            vis_prev = (st.sh_dist > 0.0) & (sh_prim < 0)
            L = L + jnp.where(vis_prev[..., None], st.ld_pend, 0.0)
            nrays = nrays + (st.sh_dist > 0.0).astype(jnp.int32)
        else:
            hit = scene_intersect(dev, o, d, t_max, time=ray_time)
        nrays = nrays + alive.astype(jnp.int32)
        it = make_interaction(dev, hit, o, d)
        it.valid = it.valid & alive
        miss = alive & (hit.prim < 0)

        # camera-hit ray-differential footprint -> trilinear mip
        # selection (camera.cpp GenerateRayDifferential +
        # interaction.cpp ComputeDifferentials); bounce>0 vertices
        # shade at the finest level, as pbrt does for non-specular
        # continuations
        from tpu_pbrt.config import cfg

        with jax.named_scope(ph.SHADE_BSDF):
            if (self.tex_eval is not None and "tri_difT" in dev
                    and cfg.mipfilter):
                from tpu_pbrt.cameras import ray_differentials

                def cam_footprint(args):
                    o_, d_, prim_, p_, ng_, valid_ = args
                    pf_c = jnp.stack(
                        [px.astype(jnp.float32) + 0.5,
                         py.astype(jnp.float32) + 0.5], axis=-1)
                    dox, ddx, doy, ddy = ray_differentials(
                        self.scene.camera, pf_c)
                    w0 = texture_footprint(
                        dev, prim_, p_, ng_, o_, d_, dox, ddx, doy, ddy
                    )
                    return jnp.where(valid_[..., None], w0, 0.0)

                args = (o, d, hit.prim, it.p, it.ng, it.valid)
                if scalar_bounce is not None:
                    # bounce > 0 shades at the finest level (pbrt's behavior
                    # for non-specular continuations) — skip the gather +
                    # plane solves entirely on those iterations
                    width = jax.lax.cond(
                        scalar_bounce == 0,
                        cam_footprint,
                        lambda a: jnp.zeros(
                            a[3].shape[:-1] + (4,), jnp.float32
                        ),
                        args,
                    )
                else:
                    # pool mode: lanes at mixed depths share the wave, so the
                    # footprint is computed each wave and masked to the
                    # camera-hit (depth 0) lanes
                    width = jnp.where(
                        (depth == 0)[..., None], cam_footprint(args), 0.0
                    )
            else:
                width = None

        # ---- emitted radiance with forward MIS ----------------------
        with jax.named_scope(ph.SHADE_EMIT):
            if "envmap" in dev:
                le_env = ld.env_lookup(dev, d)
                pdf_env = ld.infinite_pdf(dev, self.light_distr, d, ref_p=prev_p)
                w_env = jnp.where(
                    specular, 1.0, power_heuristic(1.0, prev_pdf, 1.0, pdf_env)
                )
                L = L + jnp.where(miss[..., None], beta * le_env * w_env[..., None], 0.0)
            hit_light = jnp.where(it.valid, it.light, -1)
            le = ld.emitted_radiance(dev, hit_light, it.wo, it.ng)
            pdf_light = ld.emitted_pdf(dev, self.light_distr, prev_p, it.p, hit_light, it.ng)
            w_emit = jnp.where(specular, 1.0, power_heuristic(1.0, prev_pdf, 1.0, pdf_light))
            L = L + beta * le * w_emit[..., None]

        alive = alive & (hit.prim >= 0)
        # pbrt: the vertex at bounces == maxDepth emits but neither
        # samples lights nor continues
        can_scatter = depth < self.max_depth

        with jax.named_scope(ph.SHADE_BSDF):
            mp = self.mat_at(
                dev, it, width,
                u_mix=self.u1d(px, py, s, salt + DIM_MIX),
            )
            is_null = it.valid & (mp.mtype == MAT_NONE) if self.margin else None
        # ---- NEE: light-sampling half --------------------------------
        with jax.named_scope(ph.SHADE_NEE):
            u_pick = self.u1d(px, py, s, salt + DIM_LIGHT_PICK)
            u1, u2 = self.u2d(px, py, s, salt + DIM_LIGHT_UV)
            ls = ld.sample_one_light(dev, self.light_distr, it.p, u_pick, u1, u2)
            wo_l = to_local(it.wo, it.ss, it.ts, it.ns)
            wi_l = to_local(ls.wi, it.ss, it.ts, it.ns)
            f, bsdf_pdf = bxdf.bsdf_eval(mp, wo_l, wi_l)
            f = f * jnp.abs(dot(ls.wi, it.ns))[..., None]
            do_nee = (
                it.valid
                & can_scatter
                & (ls.pdf > 0.0)
                & (jnp.max(f, axis=-1) > 0.0)
                & (jnp.max(ls.li, axis=-1) > 0.0)
            )
            o_sh = offset_ray_origin(it.p, it.ng, ls.wi)
            sh_dist = jnp.where(do_nee, ls.dist, -1.0)  # fast-exit dead lanes
            w_l = jnp.where(ls.is_delta, 1.0, power_heuristic(1.0, ls.pdf, 1.0, bsdf_pdf))
            Ld = f * ls.li * (w_l / jnp.maximum(ls.pdf, 1e-20))[..., None]
            if fused:
                # queue the shadow ray; it rides the NEXT iteration's fused
                # wave (the 0.999 dist margin matches unoccluded_tr)
                sh_o_n = o_sh
                sh_d_n = ls.wi
                sh_dist_n = jnp.where(do_nee, sh_dist * 0.999, -1.0)
                ld_pend_n = jnp.where(do_nee[..., None], beta * Ld, 0.0)
            else:
                visible, _ = unoccluded_tr(
                    dev, o_sh, ls.wi, sh_dist, None, px, py, s,
                    salt + DIM_LIGHT_UV + 200, segments=self.vis_segments,
                )
                nrays = nrays + do_nee.astype(jnp.int32)
                L = L + jnp.where((do_nee & visible)[..., None], beta * Ld, 0.0)

        # ---- continuation: BSDF sample -------------------------------
        with jax.named_scope(ph.SHADE_BSDF):
            ul = self.u1d(px, py, s, salt + DIM_BSDF_LOBE)
            ub1, ub2 = self.u2d(px, py, s, salt + DIM_BSDF_UV)
            bs = bxdf.bsdf_sample(mp, wo_l, ul, ub1, ub2)
            wi_w = normalize(to_world(bs.wi, it.ss, it.ts, it.ns))
            cont = it.valid & can_scatter & (bs.pdf > 0.0) & (jnp.max(bs.f, axis=-1) > 0.0)
            throughput = bs.f * (jnp.abs(dot(wi_w, it.ns)) / jnp.maximum(bs.pdf, 1e-20))[..., None]
            beta = jnp.where(cont[..., None], beta * throughput, beta)
            # eta^2 tracking for RR (path.cpp etaScale)
            eta2 = (mp.eta[..., 0]) ** 2
            going_in = dot(it.wo, it.ns) > 0.0
            scale = jnp.where(going_in, eta2, 1.0 / jnp.maximum(eta2, 1e-12))
            eta_scale = jnp.where(cont & bs.is_transmission, eta_scale * scale, eta_scale)

        prev_p = jnp.where(cont[..., None], it.p, prev_p)
        o = jnp.where(cont[..., None], offset_ray_origin(it.p, it.ng, wi_w), o)
        d = jnp.where(cont[..., None], wi_w, d)
        prev_pdf = jnp.where(cont, bs.pdf, prev_pdf)
        specular = jnp.where(cont, bs.is_specular, specular)
        depth = depth + cont.astype(jnp.int32)
        alive = cont

        # ---- BSSRDF probe wave (bssrdf.cpp Sample_S/Sample_Sp,
        # path.cpp's bssrdf block; compiled ONLY for scenes with
        # subsurface materials). A lane whose interface sample was
        # the specular TRANSMISSION re-emerges at an exit vertex
        # found by a fixed-K probe chord: axis/channel MIS picks a
        # radius from the baked diffusion CDF, the chord is
        # intersected K times collecting same-material hits with
        # reservoir selection, and the lane continues from the exit
        # with the Sw directional lobe (NEE + cosine continuation
        # inline below — the wavefront analog of pbrt's Sw-adapter
        # BSDF at pi). Entry Fresnel rides the interface sample;
        # f*cos/pdf of the specular transmission is 1, so beta here
        # gains exactly Sp * nFound / Pdf_Sp then Sw*pi. -----------
        if "bssrdf" in dev:
            from tpu_pbrt.core.bssrdf import (
                pdf_sr,
                sample_sr,
                sr_eval,
                sw_eval,
            )
            from tpu_pbrt.core.sampling import cosine_sample_hemisphere
            from tpu_pbrt.core.smalltab import small_take

            tabS = dev["bssrdf"]
            sub = jnp.maximum(mp.sub, 0)
            sss = cont & (mp.sub >= 0) & bs.is_transmission
            ua = self.u1d(px, py, s, salt + 12)
            uc = self.u1d(px, py, s, salt + 13)
            ur_ = self.u1d(px, py, s, salt + 14)
            uphi = self.u1d(px, py, s, salt + 15)
            # probe frame: ns axis w.p. 1/2, ss/ts each 1/4
            ax0 = (ua < 0.5)[..., None]
            ax1 = ((ua >= 0.5) & (ua < 0.75))[..., None]
            vz = jnp.where(ax0, it.ns, jnp.where(ax1, it.ss, it.ts))
            vx = jnp.where(ax0, it.ss, jnp.where(ax1, it.ts, it.ns))
            vy = jnp.where(ax0, it.ts, jnp.where(ax1, it.ns, it.ss))
            ch = jnp.clip((uc * 3.0).astype(jnp.int32), 0, 2)
            r_s = sample_sr(tabS, sub, ch, ur_)
            rmax_c = jnp.take_along_axis(
                tabS.r_max[sub], ch[..., None], axis=-1
            )[..., 0]
            l_ch = 2.0 * jnp.sqrt(jnp.maximum(rmax_c**2 - r_s**2, 0.0))
            phi_s = 2.0 * jnp.pi * uphi
            start = (
                it.p
                + r_s[..., None] * (
                    jnp.cos(phi_s)[..., None] * vx
                    + jnp.sin(phi_s)[..., None] * vy
                )
                + (0.5 * l_ch)[..., None] * vz
            )
            pdir = -vz
            ok_r = sss & (r_s < rmax_c) & (l_ch > 0.0)

            cur_o = start
            t_rem = jnp.where(ok_r, l_ch, -1.0)
            n_found = jnp.zeros(shape, jnp.int32)
            sel_p, sel_ng, sel_ns = it.p, it.ng, it.ns
            sel_ss, sel_ts = it.ss, it.ts
            for k in range(4):
                hitk = scene_intersect(
                    dev, cur_o, pdir, t_rem, time=ray_time
                )
                itk = make_interaction(dev, hitk, cur_o, pdir)
                nrays = nrays + (t_rem > 0.0).astype(jnp.int32)
                m_sub = small_take(
                    dev["mat"]["sub_id"], jnp.maximum(itk.mat, 0)
                )
                matchk = itk.valid & (m_sub == sub) & ok_r
                n_found = n_found + matchk.astype(jnp.int32)
                u_res = uniform_float(px, py, s, salt + 4000 + k)
                takek = matchk & (
                    u_res * n_found.astype(jnp.float32) < 1.0
                )
                tk = takek[..., None]
                sel_p = jnp.where(tk, itk.p, sel_p)
                sel_ng = jnp.where(tk, itk.ng, sel_ng)
                sel_ns = jnp.where(tk, itk.ns, sel_ns)
                sel_ss = jnp.where(tk, itk.ss, sel_ss)
                sel_ts = jnp.where(tk, itk.ts, sel_ts)
                adv = jnp.where(itk.valid, hitk.t + 1e-4, jnp.inf)
                cur_o = cur_o + adv[..., None] * pdir
                t_rem = jnp.where(itk.valid, t_rem - adv, -1.0)

            ok_exit = ok_r & (n_found > 0)
            dvec = sel_p - it.p
            dist_s = jnp.linalg.norm(dvec, axis=-1)
            sp = sr_eval(tabS, sub, dist_s)  # (R, 3)
            # Pdf_Sp: MIS over the 3 axes x 3 channels of projected
            # radii (bssrdf.cpp Pdf_Sp)
            dl = jnp.stack(
                [dot(dvec, it.ss), dot(dvec, it.ts), dot(dvec, it.ns)],
                axis=-1,
            )
            nl = jnp.stack(
                [dot(sel_ns, it.ss), dot(sel_ns, it.ts),
                 dot(sel_ns, it.ns)], axis=-1,
            )
            rproj = jnp.stack(
                [
                    jnp.sqrt(dl[..., 1] ** 2 + dl[..., 2] ** 2),
                    jnp.sqrt(dl[..., 2] ** 2 + dl[..., 0] ** 2),
                    jnp.sqrt(dl[..., 0] ** 2 + dl[..., 1] ** 2),
                ],
                axis=-1,
            )
            ax_prob = (0.25, 0.25, 0.5)
            pdf_tot = jnp.zeros(shape, jnp.float32)
            for a in range(3):
                for c in range(3):
                    pdf_tot = pdf_tot + pdf_sr(
                        tabS, sub, jnp.full_like(ch, c), rproj[..., a]
                    ) * jnp.abs(nl[..., a]) * (ax_prob[a] / 3.0)
            ok_exit = ok_exit & (pdf_tot > 0.0) & (
                jnp.max(sp, axis=-1) > 0.0
            )
            w_sss = sp * (
                n_found.astype(jnp.float32)
                / jnp.maximum(pdf_tot, 1e-20)
            )[..., None]
            beta = jnp.where(ok_exit[..., None], beta * w_sss, beta)

            # exit-vertex NEE with the Sw lobe (pbrt's Sw adapter); the
            # adapter's eta^2 radiance-mode factor (non-symmetric
            # scattering at the refractive exit) is applied once to beta
            # here so both the NEE term and the continuation carry it
            eta_sub = tabS.eta[sub]
            beta = jnp.where(
                ok_exit[..., None], beta * (eta_sub * eta_sub)[..., None],
                beta,
            )
            ls2 = ld.sample_one_light(
                dev, self.light_distr, sel_p,
                uniform_float(px, py, s, salt + 4100),
                uniform_float(px, py, s, salt + 4101),
                uniform_float(px, py, s, salt + 4102),
            )
            cos_l = dot(ls2.wi, sel_ns)
            f_sw_l = sw_eval(eta_sub, cos_l) * jnp.maximum(cos_l, 0.0)
            do2 = (
                ok_exit & can_scatter & (ls2.pdf > 0.0) & (cos_l > 1e-6)
                & (jnp.max(ls2.li, axis=-1) > 0.0)
            )
            occ2 = scene_intersect_p(
                dev, offset_ray_origin(sel_p, sel_ng, ls2.wi), ls2.wi,
                jnp.where(do2, ls2.dist * 0.999, -1.0),
            )
            nrays = nrays + do2.astype(jnp.int32)
            w_l2 = jnp.where(
                ls2.is_delta, 1.0,
                power_heuristic(1.0, ls2.pdf, 1.0, cos_l / jnp.pi),
            )
            L = L + jnp.where(
                (do2 & ~occ2)[..., None],
                beta * f_sw_l[..., None] * ls2.li
                * (w_l2 / jnp.maximum(ls2.pdf, 1e-20))[..., None],
                0.0,
            )

            # cosine continuation from the exit with Sw weighting:
            # beta *= Sw * cos / (cos/pi) = Sw * pi
            wloc = cosine_sample_hemisphere(
                uniform_float(px, py, s, salt + 4103),
                uniform_float(px, py, s, salt + 4104),
            )
            wi2 = normalize(
                wloc[..., 0:1] * sel_ss + wloc[..., 1:2] * sel_ts
                + wloc[..., 2:3] * sel_ns
            )
            cos2 = jnp.maximum(dot(wi2, sel_ns), 1e-6)
            beta = jnp.where(
                ok_exit[..., None],
                beta * (sw_eval(eta_sub, cos2) * jnp.pi)[..., None],
                beta,
            )
            o = jnp.where(
                ok_exit[..., None],
                offset_ray_origin(sel_p, sel_ng, wi2), o,
            )
            d = jnp.where(ok_exit[..., None], wi2, d)
            prev_p = jnp.where(ok_exit[..., None], sel_p, prev_p)
            prev_pdf = jnp.where(ok_exit, cos2 / jnp.pi, prev_pdf)
            specular = specular & ~ok_exit
            alive = jnp.where(sss, ok_exit, alive)

        # ---- null passthrough (uncounted bounce, path.cpp bounces--)
        if is_null is not None:
            alive = alive | is_null
            o = jnp.where(is_null[..., None], offset_ray_origin(it.p, it.ng, d), o)
            # d/beta/prev_pdf/specular/prev_p unchanged: the crossing is
            # not a scattering event; MIS still references the last real
            # vertex

        # ---- Russian roulette. pbrt path.cpp tests `bounces > 3` at
        # the END of iteration `bounces`; our per-lane `depth` counter
        # is post-increment here (depth == bounces + 1 for a lane that
        # continued every iteration), so `depth > 4` is the SAME
        # schedule — first possible kill after the 5th real bounce is
        # sampled. depth counts REAL bounces only: null crossings must
        # not advance RR (pbrt's bounces-- semantics). ----------------
        rr_on = depth > 4
        rr_beta = jnp.max(beta, axis=-1) * eta_scale
        q = jnp.maximum(0.05, 1.0 - rr_beta)
        u_rr = uniform_float(px, py, s, salt + DIM_RR)
        rr_cand = alive & rr_on & (rr_beta < self.rr_threshold)
        kill = rr_cand & (u_rr < q)
        survive_scale = jnp.where(rr_cand & ~kill, 1.0 / jnp.maximum(1.0 - q, 1e-6), 1.0)
        beta = beta * survive_scale[..., None]
        alive = alive & ~kill

        if fused:
            pend = (sh_o_n, sh_d_n, sh_dist_n, ld_pend_n)
        else:
            pend = (st.sh_o, st.sh_d, st.sh_dist, st.ld_pend)
        if ctr is not None:
            from tpu_pbrt.obs import counters as obs_counters

            ctr = obs_counters.bounce_update(
                ctr, alive=st.alive, rays_before=nrays_in, rays_after=nrays,
                pairs_per_lane=PAIRS_PER_BOUNCE,
            )
            ctr = obs_counters.trace_update(ctr, work)
            if ctr.lt_picks is not None:
                ctr = obs_counters.light_update(
                    ctr, picking=it.valid & can_scatter, emitting=it.valid,
                    pick_reads=ld.pick_reads(dev, self.light_distr),
                    emit_reads=ld.emit_reads(dev, self.light_distr),
                )
        return LaneSt(
            o, d, L, beta, alive, depth, prev_pdf, specular, eta_scale,
            prev_p, *pend,
        ), nrays, ctr

    # -- fixed-batch loop (TPU_PBRT_REGEN=0 fallback; non-fused scenes) ----
    def li(self, dev, o, d, px, py, s):
        shape = o.shape[:-1]
        # motion blur: one shutter time per camera sample, fixed along
        # the whole path (CameraSample::time); keyframes are the shutter
        # endpoints, so the normalized time IS the sample
        if "tri_verts1" in dev:
            ray_time = self.u1d(px, py, s, DIM_TIME)
        else:
            ray_time = None
        max_iters = self.max_depth + 1 + self.margin
        # Fused-wave mode (the stream tracer's costs are per-WAVE fixed +
        # per-pair): each iteration traces [continuation; previous bounce's
        # shadow ray] as ONE 2R batch, halving the wave count. The shadow
        # contribution lands one iteration late (pure pipelining — the
        # estimator is unchanged). Scenes with null-interface materials
        # need the multi-segment Tr walk and keep split waves.
        fused = self.vis_segments == 1 and self.margin == 0

        class St(NamedTuple):
            bounce: jnp.ndarray  # scalar: loop iteration (= sampler salt base)
            nrays: jnp.ndarray
            lane: LaneSt

        def cond(st: St):
            live = jnp.any(st.lane.alive)
            if fused:
                # one extra iteration may be needed to settle the last
                # pending shadow ray
                return (st.bounce < max_iters + 1) & (
                    live | jnp.any(st.lane.sh_dist > 0.0)
                )
            return (st.bounce < max_iters) & live

        def body(st: St):
            salt = st.bounce * DIMS_PER_BOUNCE
            lane, nrays, _ = self._bounce_wave(
                dev, px, py, s, salt, ray_time, st.lane, st.nrays,
                fused=fused, scalar_bounce=st.bounce,
            )
            return St(st.bounce + 1, nrays, lane)

        init = St(
            bounce=jnp.int32(0),
            nrays=jnp.zeros(shape, jnp.int32),
            lane=fresh_lanes(o, d),
        )
        out = jax.lax.while_loop(cond, body, vary(init))
        return out.lane.L, out.nrays

    # -- persistent wavefront: regeneration in place -----------------------
    def pool_chunk(self, dev, fs: FilmState, start_pix, start_s,
                   n_work: int, pool: int, film=None, cam=None,
                   nan_wave=None, work_offset=None):
        """Drain `n_work` work items from start on through a resident
        pool of `pool` path slots, one bounce per wave: the consecutive
        items [start, start + n_work), or, where a mesh deals a dispatch
        out in granules, item `start + work_offset(k)` for the drain's
        k-th (parallel/mesh.work_item; None traces nothing).

        Per wave: (1) REGENERATE — every free slot takes a fresh camera
        ray from the chunk's work counter where it lies: the k-th free
        slot in lane order takes work item `cursor + k`
        (`_free_slot_work`, a prefix count of the free mask), live lanes
        stay in their slots, so the trace/shade wave that follows runs
        near-full (every consumer masks or sorts for itself: the stream
        tracer seeds its own order, the deposit has its own sort);
        (2) one `_bounce_wave`; (3) DEPOSIT — lanes that
        finished this wave (dead, no pending shadow) scatter their L into
        the film state and release their slot. A lane killed with a
        shadow ray still in flight stays resident one extra wave (the
        fused layout settles NEE one wave late) before depositing.

        Returns (film_state, rays_traced, live_lane_waves, n_waves,
        truncated, counters): mean wave occupancy = live_lane_waves /
        (n_waves * pool); truncated is 1 if the max_waves safety cutoff
        fired with work still outstanding (the caller warns loudly — a
        silently darker image must never pass as a completed render);
        counters is the telemetry WaveCounters block carried through the
        drain (None under TPU_PBRT_TELEMETRY=0 — an empty pytree leaf,
        so the killed program is the exact pre-telemetry one).

        nan_wave is the chaos-injection seam (tpu_pbrt/chaos `nan:wave`):
        a traced int32 scalar naming the wave whose active lanes get
        their radiance replaced with NaN (-1 = clean dispatch — the host
        passes -1 on every re-dispatch after the fault fired, so exact
        recovery needs no recompile). None (no nan site in the plan)
        compiles no injection code at all.
        """
        from tpu_pbrt.config import cfg
        from tpu_pbrt.obs import counters as obs_counters

        assert pool < (1 << _POOL_LANE_BITS)
        film = film if film is not None else self.scene.film
        cam = cam if cam is not None else self.scene.camera
        x0, x1, y0, y1 = film.sample_bounds()
        w = x1 - x0
        npix = w * (y1 - y0)
        spp = self.spp
        motion = "tri_verts1" in dev
        box_fast = film.pixel_deposit_ok()
        # Segmented deposit (ROADMAP "pool deposit path" carried item):
        # the in-loop film scatter ran full-pool-width per wave although
        # only the terminated lanes carry a deposit. One extra packed-i32
        # single-key sort (the stream tracer's fast path) moves this wave's
        # terminated lanes to a contiguous prefix and only a static
        # `seg`-wide window is gathered + scattered — ~pool/seg less
        # scatter traffic per wave; a rare wave where more than `seg`
        # lanes terminate at once falls back to the full-width scatter
        # (lax.cond in the body), so drain length and occupancy are
        # untouched. seg >= pool compiles the exact pre-segment program
        # (no sort, no cond).
        seg = int(cfg.deposit_seg)
        if seg == 0:
            seg = pool // 4 if pool >= 256 else pool
        if seg < 0 or seg > pool:
            seg = pool
        seg = max(seg, 1)
        # worst case: every refill round runs every lane to max_depth,
        # plus the shadow-settle wave — a static safety bound only
        max_waves = (n_work // pool + 2) * (self.max_depth + 2) + 8

        class PSt(NamedTuple):
            fs: FilmState
            lane: LaneSt
            px: jnp.ndarray
            py: jnp.ndarray
            s: jnp.ndarray
            wt: jnp.ndarray  # camera ray weight (realistic lens vignetting)
            time: jnp.ndarray  # per-lane shutter time (motion scenes)
            has_work: jnp.ndarray  # slot holds an undeposited work item
            cursor: jnp.ndarray  # work items consumed so far
            nrays: jnp.ndarray
            live: jnp.ndarray  # sum of live lanes over waves (occupancy)
            waves: jnp.ndarray
            ctr: Any  # WaveCounters | None (None = telemetry killed)

        def cond(ps: PSt):
            return ((ps.cursor < n_work) | jnp.any(ps.has_work)) & (
                ps.waves < max_waves
            )

        def body(ps: PSt):
            # ---- regeneration in place: a free slot takes the work item
            # of its rank among the free slots; no lane moves ----------
            with jax.named_scope(ph.POOL_REGEN):
                widx, can, consumed = _free_slot_work(
                    ps.has_work, ps.cursor, n_work
                )
                widx = jnp.where(can, widx, 0)
                if work_offset is not None:
                    widx = work_offset(widx)
                valid, pxn, pyn, sn, _, o_n, d_n, wt_n = self.work_to_rays(
                    cam, spp, x0, y0, w, npix, start_pix, start_s, widx,
                )
                can = can & valid
                fresh = fresh_lanes(o_n, d_n)
                lane = jax.tree.map(
                    lambda new, old: jnp.where(
                        can.reshape((pool,) + (1,) * (new.ndim - 1)), new, old
                    ),
                    fresh, ps.lane,
                )
                px = jnp.where(can, pxn, ps.px)
                py = jnp.where(can, pyn, ps.py)
                s = jnp.where(can, sn, ps.s)
                wt = jnp.where(can, wt_n, ps.wt)
                tl = ps.time
                if motion:
                    tl = jnp.where(can, self.u1d(pxn, pyn, sn, DIM_TIME), tl)
                has_work = ps.has_work | can

                live = ps.live + jnp.sum(lane.alive, dtype=jnp.int32)
                alive_pre = lane.alive

            # ---- one bounce wave -------------------------------------
            salt = lane.depth * DIMS_PER_BOUNCE
            with jax.named_scope(ph.POOL_BOUNCE):
                lane, nray_d, ctr = self._bounce_wave(
                    dev, px, py, s, salt, tl if motion else None, lane,
                    jnp.zeros((pool,), jnp.int32), fused=True,
                    scalar_bounce=None, ctr=ps.ctr,
                )

            if nan_wave is not None:
                # chaos nan:wave injection — contaminate every resident
                # lane's radiance on the named wave. The NaNs ride the
                # lanes to their deposit wave (NaN + x = NaN), where the
                # film firewall scrubs and counts them
                poison = has_work & (ps.waves == nan_wave)
                lane = lane._replace(
                    L=jnp.where(
                        poison[..., None], jnp.float32(jnp.nan), lane.L
                    )
                )

            # ---- scatter-on-terminate film deposit -------------------
            with jax.named_scope(ph.POOL_DEPOSIT):
                done = has_work & ~lane.alive & ~(lane.sh_dist > 0.0)
                if ctr is not None:
                    from tpu_pbrt.core.film import nonfinite_mask

                    # structural drain counters (rays/occupancy were folded
                    # in by _bounce_wave): all pure in-loop i32 reductions,
                    # fetched once at the drain boundary with the rest of aux.
                    # nonfinite counts the deposits the film firewall is
                    # about to scrub — same predicate the deposit uses, so
                    # the count and the scrub can never disagree
                    ctr = obs_counters.pool_update(
                        ctr,
                        regenerated=jnp.sum(can, dtype=jnp.int32),
                        terminated=jnp.sum(
                            alive_pre & ~lane.alive, dtype=jnp.int32
                        ),
                        deposits=jnp.sum(done, dtype=jnp.int32),
                        nonfinite=jnp.sum(
                            done & nonfinite_mask(lane.L), dtype=jnp.int32
                        ),
                    )
                if not box_fast:
                    # general filter footprint: recompute the film jitter
                    # (a pure function of the work item) and mask the
                    # not-yet-terminated lanes out of the crop window
                    fx, fy = self.film_jitter(px, py, s)
                    p_film = jnp.stack(
                        [px.astype(jnp.float32) + fx,
                         py.astype(jnp.float32) + fy], axis=-1,
                    )
                if seg < pool:
                    # SEGMENTED deposit: one more packed-i32 single-key sort
                    # (the stream tracer's fast path) moves this wave's
                    # terminated lanes to a contiguous prefix — stable on
                    # lane index, so the gathered batch deposits in exactly
                    # the full-width scatter's relative order (bit-identity)
                    # — and only a static `seg`-wide window is scattered.
                    # The rare wave where MORE than `seg` lanes terminate at
                    # once takes the full-width branch of the lax.cond
                    # instead, so no lane ever waits for a window slot (a
                    # deferred-deposit design measurably stalled
                    # regeneration: occupancy 0.52 vs 0.96 on the depth-5
                    # occupancy scene).
                    dkey = jnp.arange(pool, dtype=jnp.int32) | jnp.where(
                        done, 0, jnp.int32(1) << _POOL_LANE_BITS
                    )
                    (dkey_s,) = jax.lax.sort([dkey], num_keys=1)
                    dperm = (dkey_s & ((1 << _POOL_LANE_BITS) - 1))[:seg]
                    dmask = jnp.take(done, dperm)

                    if box_fast:

                        def _dep_seg(fs0):
                            return film.add_samples_pixel(
                                fs0, jnp.take(px, dperm), jnp.take(py, dperm),
                                jnp.take(lane.L, dperm, axis=0), dmask,
                                jnp.take(wt, dperm),
                            )

                        def _dep_full(fs0):
                            return film.add_samples_pixel(
                                fs0, px, py, lane.L, done, wt
                            )

                    else:

                        def _dep_seg(fs0):
                            return film.add_samples(
                                fs0,
                                jnp.where(
                                    dmask[..., None],
                                    jnp.take(p_film, dperm, axis=0), -1e6,
                                ),
                                jnp.take(lane.L, dperm, axis=0),
                                jnp.take(wt, dperm),
                            )

                        def _dep_full(fs0):
                            return film.add_samples(
                                fs0,
                                jnp.where(done[..., None], p_film, -1e6),
                                lane.L, wt,
                            )

                    fs = jax.lax.cond(
                        jnp.sum(done, dtype=jnp.int32) <= seg,
                        _dep_seg, _dep_full, ps.fs,
                    )
                elif box_fast:
                    # box(0.5): one masked own-pixel scatter, matching the
                    # aligned path the fixed-batch single-device render uses
                    fs = film.add_samples_pixel(ps.fs, px, py, lane.L, done, wt)
                else:
                    fs = film.add_samples(
                        ps.fs, jnp.where(done[..., None], p_film, -1e6),
                        lane.L, wt,
                    )
            return PSt(
                fs=fs, lane=lane, px=px, py=py, s=s, wt=wt, time=tl,
                has_work=has_work & ~done,
                cursor=ps.cursor + consumed,
                nrays=ps.nrays + jnp.sum(nray_d),
                live=live,
                waves=ps.waves + 1,
                ctr=ctr,
            )

        zero3 = jnp.zeros((pool, 3), jnp.float32)
        unit_d = jnp.broadcast_to(
            jnp.asarray([0.0, 0.0, 1.0], jnp.float32), (pool, 3)
        )
        init = PSt(
            fs=fs,
            lane=fresh_lanes(zero3, unit_d)._replace(
                alive=jnp.zeros((pool,), bool)
            ),
            px=jnp.zeros((pool,), jnp.int32),
            py=jnp.zeros((pool,), jnp.int32),
            s=jnp.zeros((pool,), jnp.int32),
            wt=jnp.zeros((pool,), jnp.float32),
            time=jnp.zeros((pool,), jnp.float32),
            has_work=jnp.zeros((pool,), bool),
            cursor=jnp.int32(0),
            nrays=jnp.int32(0),
            live=jnp.int32(0),
            waves=jnp.int32(0),
            ctr=obs_counters.maybe_zeros(
                stream="tstream" in dev, halton=self.skind == "halton",
                light="rows" in dev["light"],
            ),
        )
        with jax.named_scope(ph.POOL_LOOP):
            out = jax.lax.while_loop(cond, body, vary(init))
        truncated = (
            (out.cursor < n_work) | jnp.any(out.has_work)
        ).astype(jnp.int32)
        return out.fs, out.nrays, out.live, out.waves, truncated, out.ctr
