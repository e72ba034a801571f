"""MLTIntegrator — primary-sample-space Metropolis light transport.

Capability match for pbrt-v3 src/integrators/mlt.{h,cpp}: the MLTSampler
primary-sample vector with large-step/small-step mutations (mlt.cpp
MLTSampler::Accept/Reject, the exponential small-step kernel), the
bootstrap phase whose luminances build a Distribution1D and the b
normalization constant, parallel Markov chains, Kelemen-weighted
splat-only film accumulation, and the final b/mutationsPerPixel scaling.

TPU-first redesign:
- pbrt runs nChains sequential chains on worker threads; here EVERY lane
  of a (C,) batch is an independent chain — one jitted mutation step
  advances all chains at once, and the film splats of a whole step land
  in one scatter-add.
- the primary sample vector is an explicit (C, D) matrix; the path
  contribution function f(U) re-traces the unidirectional path estimator
  (path.py's NEE + forward-MIS scheme) with every random dimension read
  from U instead of the counter RNG — so MLT means match `path` means,
  which is the cross-convergence oracle.

Documented deviation: pbrt layers PSSMLT over the BDPT strategy space
(multiplexed MLT, one (s,t) strategy per chain depth); this
implementation mutates the unidirectional path space (Kelemen et al.'s
original PSSMLT). Equal-flight-time caustic performance is weaker; the
sampler/bootstrap/chain machinery — what mlt.cpp adds over bdpt.cpp — is
equivalent.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from tpu_pbrt.cameras import generate_rays
from tpu_pbrt.core import bxdf
from tpu_pbrt.core import lights_dev as ld
from tpu_pbrt.core.sampling import hash_u32, power_heuristic, uniform_float
from tpu_pbrt.core.vecmath import (
    dot,
    normalize,
    offset_ray_origin,
    to_local,
    to_world,
)
from tpu_pbrt.integrators.common import (
    RenderResult,
    WavefrontIntegrator,
    make_interaction,
    scene_intersect,
    scene_intersect_p,
)
from tpu_pbrt.parallel.mesh import vary

#: dims consumed per bounce: light pick + light uv2 + bsdf lobe + bsdf uv2 + rr
_DIMS_PER_BOUNCE = 8  # [light pick/uv(3), bsdf(3), rr, mix]
_DIMS_CAMERA = 4  # film xy + lens uv


def _luminance(c):
    return 0.2126 * c[..., 0] + 0.7152 * c[..., 1] + 0.0722 * c[..., 2]


class MLTIntegrator(WavefrontIntegrator):
    name = "mlt"
    rays_per_camera_ray = 3.0

    def __init__(self, params, scene, options):
        super().__init__(params, scene, options)
        self.max_depth = params.find_one_int("maxdepth", 5)
        self.n_bootstrap = params.find_one_int("bootstrapsamples", 100000)
        self.n_chains = params.find_one_int("chains", 4096)
        self.mutations_per_pixel = params.find_one_int("mutationsperpixel", 100)
        self.sigma = params.find_one_float("sigma", 0.01)
        self.large_step_prob = params.find_one_float("largestepprobability", 0.3)
        self.n_dims = _DIMS_CAMERA + _DIMS_PER_BOUNCE * self.max_depth
        from tpu_pbrt.utils.error import Warning as _W

        if scene.has_null_materials:
            _W("mlt: null-interface materials are traversed as opaque")

    # ------------------------------------------------------------------
    # f(U): path contribution from an explicit primary-sample matrix
    # ------------------------------------------------------------------
    def _f(self, dev, U):
        """U: (C, D) in [0,1). Returns (p_film (C,2) raster, L (C,3))."""
        scene = self.scene
        film = scene.film
        x0, x1, y0, y1 = film.sample_bounds()
        w = x1 - x0
        h = y1 - y0
        p_film = jnp.stack(
            [x0 + U[:, 0] * w, y0 + U[:, 1] * h], axis=-1
        )
        o, d, wt = generate_rays(scene.camera, p_film, U[:, 2:4])
        C = U.shape[0]
        L = jnp.zeros((C, 3), jnp.float32)
        beta = wt[..., None] * jnp.ones((C, 3), jnp.float32)
        alive = jnp.ones((C,), bool)
        specular = jnp.ones((C,), bool)
        prev_pdf = jnp.zeros((C,), jnp.float32)
        prev_p = o
        # rolled depth loop: one bsdf/light-sampling instantiation for all
        # depths (XLA compile time is superlinear in module size; the
        # unrolled form dominated the MLT tests' wall time)
        def body(depth, carry):
            o, d, L, beta, alive, specular, prev_pdf, prev_p = carry
            t_max = jnp.where(alive, jnp.inf, -1.0)
            hit = scene_intersect(dev, o, d, t_max)
            it = make_interaction(dev, hit, o, d)
            it.valid = it.valid & alive
            miss = alive & (hit.prim < 0)
            if "envmap" in dev:
                le_env = ld.env_lookup(dev, d)
                pdf_env = ld.infinite_pdf(dev, self.light_distr, d, ref_p=prev_p)
                w_env = jnp.where(
                    specular, 1.0, power_heuristic(1.0, prev_pdf, 1.0, pdf_env)
                )
                L = L + jnp.where(miss[..., None], beta * le_env * w_env[..., None], 0.0)
            hit_light = jnp.where(it.valid, it.light, -1)
            le = ld.emitted_radiance(dev, hit_light, it.wo, it.ng)
            pdf_light = ld.emitted_pdf(
                dev, self.light_distr, prev_p, it.p, hit_light, it.ng
            )
            w_emit = jnp.where(
                specular, 1.0, power_heuristic(1.0, prev_pdf, 1.0, pdf_light)
            )
            L = L + beta * le * w_emit[..., None]
            alive = alive & (hit.prim >= 0)
            base = _DIMS_CAMERA + depth * _DIMS_PER_BOUNCE
            Ub = jax.lax.dynamic_slice(
                U, (jnp.int32(0), base), (C, _DIMS_PER_BOUNCE)
            )
            scatter_ok = alive & (depth < self.max_depth)
            # mix selection rides its own PSS dimension so f(U) stays
            # a deterministic function of U (detailed balance needs it)
            mp = self.mat_at(dev, it, u_mix=Ub[:, 7])
            wo_l = to_local(it.wo, it.ss, it.ts, it.ns)
            # NEE light-sampling half (MIS vs BSDF pdf, as in path.py)
            ls = ld.sample_one_light(
                dev, self.light_distr, it.p, Ub[:, 0], Ub[:, 1], Ub[:, 2]
            )
            wi_l = to_local(ls.wi, it.ss, it.ts, it.ns)
            f_l, pdf_b = bxdf.bsdf_eval(mp, wo_l, wi_l)
            f_l = f_l * jnp.abs(dot(ls.wi, it.ns))[..., None]
            do_l = (
                it.valid
                & scatter_ok
                & (ls.pdf > 0.0)
                & (jnp.max(f_l, axis=-1) > 0.0)
                & (jnp.max(ls.li, axis=-1) > 0.0)
            )
            o_s = offset_ray_origin(it.p, it.ng, ls.wi)
            occluded = scene_intersect_p(
                dev, o_s, ls.wi, jnp.where(do_l, ls.dist * 0.999, -1.0)
            )
            w_l = jnp.where(
                ls.is_delta, 1.0, power_heuristic(1.0, ls.pdf, 1.0, pdf_b)
            )
            contrib = f_l * ls.li * (w_l / jnp.maximum(ls.pdf, 1e-20))[..., None]
            L = L + jnp.where((do_l & ~occluded)[..., None], beta * contrib, 0.0)
            # BSDF continuation
            bs = bxdf.bsdf_sample(mp, wo_l, Ub[:, 3], Ub[:, 4], Ub[:, 5])
            wi_w = normalize(to_world(bs.wi, it.ss, it.ts, it.ns))
            cont = scatter_ok & (bs.pdf > 0.0) & (jnp.max(bs.f, axis=-1) > 0.0)
            thr = bs.f * (jnp.abs(dot(wi_w, it.ns)) / jnp.maximum(bs.pdf, 1e-20))[..., None]
            beta = jnp.where(cont[..., None], beta * thr, beta)
            specular = bs.is_specular
            prev_pdf = jnp.where(bs.is_specular, 0.0, bs.pdf)
            prev_p = jnp.where(cont[..., None], it.p, prev_p)
            o = jnp.where(cont[..., None], offset_ray_origin(it.p, it.ng, wi_w), o)
            d = jnp.where(cont[..., None], wi_w, d)
            alive = cont
            # Russian roulette after depth 3 (path.cpp bounces > 3)
            do_rr = depth >= 3
            q = jnp.where(
                do_rr, jnp.maximum(0.05, 1.0 - jnp.max(beta, axis=-1)), 0.0
            )
            survive = Ub[:, 6] >= q
            beta = jnp.where(
                (alive & survive & do_rr)[..., None],
                beta / jnp.maximum(1.0 - q, 1e-6)[..., None],
                beta,
            )
            alive = alive & survive
            return o, d, L, beta, alive, specular, prev_pdf, prev_p

        carry = (o, d, L, beta, alive, specular, prev_pdf, prev_p)
        _, _, L, *_ = jax.lax.fori_loop(
            0, self.max_depth + 1, body, vary(carry)
        )
        return p_film, jnp.maximum(L, 0.0)

    # ------------------------------------------------------------------
    def render(self, scene=None, mesh=None, max_seconds: float = 0.0, **kw) -> RenderResult:
        scene = scene or self.scene
        dev = scene.dev
        film = scene.film
        x0, x1, y0, y1 = film.sample_bounds()
        w = x1 - x0
        h = y1 - y0
        npix = w * h
        D = self.n_dims
        C = self.n_chains
        total_mutations = npix * self.mutations_per_pixel
        n_steps = max(total_mutations // C, 1)

        # ---- bootstrap (mlt.cpp "Generate bootstrap samples") ----------
        nb = self.n_bootstrap
        bid = jnp.arange(nb, dtype=jnp.int32)

        @jax.jit
        def bootstrap_eval(salt):
            U = jnp.stack(
                [uniform_float(bid, bid * 7 + 3, salt, k) for k in range(D)], -1
            )
            _, L = self._f(dev, U)
            return _luminance(L), U

        y_boot, U_boot = bootstrap_eval(jnp.int32(0x8F2))
        y_np = np.asarray(y_boot, np.float64)
        b = float(y_np.mean())  # the normalization constant (E[y] estimate)
        if b <= 0.0:
            # black scene: nothing to mutate toward
            img = np.zeros((h, w, 3), np.float32)
            return RenderResult(
                image=img, film_state=None, seconds=0.0, rays_traced=nb,
                mray_per_sec=0.0, spp=self.mutations_per_pixel,
            )
        # chain seeds ~ y (Distribution1D over bootstrap luminances)
        p = y_np / y_np.sum()
        rng = np.random.default_rng(0x51F0)
        seeds = rng.choice(nb, size=C, p=p)
        U_cur = jnp.asarray(np.asarray(U_boot)[seeds])

        # ---- chains ----------------------------------------------------
        pL = self.large_step_prob
        sigma = self.sigma

        from functools import partial

        def chain_steps_body(U_cur, p_cur, L_cur, y_cur, splat_img, step0,
                             n_inner, cid0=0):
            n_local = U_cur.shape[0]

            def one(carry, step):
                U_cur, p_cur, L_cur, y_cur, splat = carry
                cid = cid0 + jnp.arange(n_local, dtype=jnp.int32)

                def u(salt):
                    return uniform_float(cid, step, jnp.int32(0x3D7), salt)

                large = u(0) < pL
                # small step: pbrt's exponential-scale symmetric kernel
                Un = jnp.stack([u(100 + k) for k in range(D)], -1)
                eps = jnp.stack([u(300 + k) for k in range(D)], -1)
                mag = sigma * jnp.exp(-jnp.log(1024.0) * eps)
                delta = jnp.where(Un < 0.5, mag, -mag)
                U_small = (U_cur + delta) % 1.0
                U_prop = jnp.where(large[:, None], Un, U_small)
                p_prop, L_prop = self._f(dev, U_prop)
                y_prop = _luminance(L_prop)
                a = jnp.minimum(1.0, y_prop / jnp.maximum(y_cur, 1e-20))
                # Kelemen weights (mlt.cpp "Compute acceptance probability")
                w_new = (a + large.astype(jnp.float32)) / (
                    y_prop / b + pL
                )
                w_old = (1.0 - a) / (y_cur / b + pL)

                def splat_to(splat, pf, val):
                    px = jnp.clip(pf[:, 0].astype(jnp.int32) - x0, 0, w - 1)
                    py = jnp.clip(pf[:, 1].astype(jnp.int32) - y0, 0, h - 1)
                    idx = py * w + px
                    ok = jnp.isfinite(val).all(-1) & (jnp.max(val, -1) >= 0.0)
                    return splat.at[jnp.where(ok, idx, npix)].add(
                        jnp.where(ok[:, None], val, 0.0), mode="drop"
                    )

                splat = splat_to(splat, p_prop, L_prop * w_new[:, None])
                splat = splat_to(splat, p_cur, L_cur * w_old[:, None])
                accept = u(700) < a
                U_cur = jnp.where(accept[:, None], U_prop, U_cur)
                p_cur = jnp.where(accept[:, None], p_prop, p_cur)
                L_cur = jnp.where(accept[:, None], L_prop, L_cur)
                y_cur = jnp.where(accept, y_prop, y_cur)
                return (U_cur, p_cur, L_cur, y_cur, splat), accept.mean()

            (U_cur, p_cur, L_cur, y_cur, splat_img), acc = jax.lax.scan(
                one,
                vary((U_cur, p_cur, L_cur, y_cur, splat_img)),
                step0 + jnp.arange(n_inner, dtype=jnp.int32),
            )
            return U_cur, p_cur, L_cur, y_cur, splat_img, acc.mean()

        if mesh is not None and mesh.devices.size > 1:
            # chains shard over the mesh with GLOBAL chain ids (the shard
            # union is exactly the single-device chain set); each device
            # splats its chains into a full-image plane that psum-merges
            # over ICI at the end of every outer block
            from jax.sharding import NamedSharding, PartitionSpec as PS

            from tpu_pbrt.parallel.mesh import TILE_AXIS, shard_map

            n_dev = int(mesh.devices.size)
            pad_c = (-C) % n_dev
            if pad_c:
                # seed pad rows from DISTINCT bootstrap states (wrap
                # around the chain set) — duplicating chain 0 would
                # over-represent one start state in the initial
                # distribution (small transient bias on short runs)
                wrap = jnp.arange(pad_c, dtype=jnp.int32) % C
                U_cur = jnp.concatenate([U_cur, U_cur[wrap]])
            C_tot = C + pad_c
            cpd = C_tot // n_dev
            U_cur = jax.device_put(
                U_cur, NamedSharding(mesh, PS(TILE_AXIS))
            )

            _specs = dict(
                mesh=mesh,
                in_specs=(
                    PS(),
                    (PS(TILE_AXIS), PS(TILE_AXIS), PS(TILE_AXIS),
                     PS(TILE_AXIS)),
                    PS(),
                    PS(),
                ),
                out_specs=(
                    (PS(TILE_AXIS), PS(TILE_AXIS), PS(TILE_AXIS),
                     PS(TILE_AXIS)),
                    PS(),
                    PS(),
                ),
            )

            def make_steps_shard(n_inner_static):
                def steps_shard(dev_, carry, splat_in, step0):
                    u_, p_, l_, y_ = carry
                    didx = jax.lax.axis_index(TILE_AXIS)
                    u_, p_, l_, y_, delta, acc = chain_steps_body(
                        u_, p_, l_, y_, jnp.zeros_like(splat_in), step0,
                        n_inner_static, cid0=didx * cpd,
                    )
                    delta = jax.lax.psum(delta, TILE_AXIS)
                    acc = jax.lax.pmean(acc, TILE_AXIS)
                    return (u_, p_, l_, y_), splat_in + delta, acc

                return jax.jit(shard_map(steps_shard, **_specs))

            # one compiled step function per distinct n_inner (honoring
            # the argument exactly like the single-device static arg)
            _jit_steps_cache = {}

            def chain_steps(U_c, p_c, L_c, y_c, splat_img, step0, n_inner):
                fn = _jit_steps_cache.get(n_inner)
                if fn is None:
                    fn = make_steps_shard(n_inner)
                    _jit_steps_cache[n_inner] = fn
                carry, splat_img, acc = fn(
                    dev, (U_c, p_c, L_c, y_c), splat_img, step0
                )
                return (*carry, splat_img, acc)

            # padded chains are real chains (duplicated seeds) and their
            # mutations add energy: renormalize by the true chain count
            C = C_tot
        else:
            chain_steps = jax.jit(
                partial(chain_steps_body, cid0=0),
                static_argnames=("n_inner",),
            )

        p_cur, L_cur = jax.jit(self._f)(dev, U_cur)
        y_cur = _luminance(L_cur)
        splat = jnp.zeros((npix, 3), jnp.float32)

        from tpu_pbrt.utils.stats import STATS, ProgressReporter

        inner = 16
        n_outer = max(n_steps // inner, 1)
        progress = ProgressReporter(
            n_outer, "MLT", quiet=bool(getattr(self.options, "quiet", False))
        )
        t0 = time.time()
        done_steps = 0
        acc_rate = 0.0
        with STATS.phase("Integrator/MLT render"):
            for outer in range(n_outer):
                U_cur, p_cur, L_cur, y_cur, splat, acc_rate = chain_steps(
                    U_cur, p_cur, L_cur, y_cur, splat,
                    jnp.int32(outer * inner), inner,
                )
                done_steps += inner
                progress.update()
                if max_seconds > 0 and time.time() - t0 > max_seconds:
                    break
        progress.done()
        secs = time.time() - t0
        STATS.distribution("MLT/Acceptance rate", float(acc_rate))

        # final estimate: splat average scaled by b (film.cpp WriteImage's
        # splatScale = b / mutationsPerPixel, with the per-pixel mutation
        # count expressed through the splat normalization below)
        n_done = done_steps * C
        img = np.asarray(splat).reshape(h, w, 3) * (npix / max(n_done, 1))
        img = np.ascontiguousarray(img, np.float32)
        rays = (nb + n_done) * int(self.max_depth * 2)
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
            spp=self.mutations_per_pixel,
            completed_fraction=done_steps / max(n_steps, 1),
            stats={"b": b, "acceptance": float(acc_rate)},
        )
