"""Layer 2: jaxpr / compile-time audit of the real render entry points.

Where the AST lint reasons about source text, this layer traces the
actual programs the renderer dispatches and asserts the TPU hot-path
invariants on what XLA will really see:

- **no f64**: every aval in the jaxpr (including sub-jaxprs of
  while/cond/scan) is <= 32-bit. A single silently-promoted f64 doubles
  HBM traffic for that buffer and falls off the MXU fast path.
- **no callbacks**: no `pure_callback` / `debug_callback` / `io_callback`
  primitives — a leftover debug print in the bounce loop is a host
  round-trip per wave.
- **donation materialized**: the film/pool chunk functions are compiled
  and the executable's `input_output_alias` table must alias EVERY film
  buffer input to an output (donate_argnums that silently fails to alias
  is how PR 1's resume path double-allocated, and donating a
  numpy-aliased buffer is how it corrupted the heap).
- **zero retraces**: two same-shape waves reuse one cached executable —
  the jit cache must not grow between chunk 1 and chunk N.
- **transfer hygiene**: a smoke render completes under
  `jax.transfer_guard("disallow")` — every host<->device crossing in the
  loop is explicit (device_put/device_get), so a new implicit sync shows
  up as a hard error, not a silent stall.

Entry points audited here: the PathIntegrator fixed-batch wave and the
persistent pool drain, stream BVH traversal, the film deposit paths, and
the sharded_pool_renderer mesh step. tests/test_jaxpr_audit.py adds the
volpath/sppm/bdpt integrators (xfail where a violation is known and
ROADMAP-tracked, so the suite documents debt instead of hiding it).

Everything is pure-trace (jax.make_jaxpr) except the donation /
recompile / transfer-guard checks, which compile tiny-scene programs.
"""

from __future__ import annotations

from functools import lru_cache
from typing import List

import numpy as np


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

_CALLBACK_PRIMITIVES = {
    "pure_callback",
    "debug_callback",
    "debug_print",  # what jax.debug.print traces to since jax 0.8
    "io_callback",
    "outside_call",
}


def _sub_jaxprs(v):
    from jax.extend import core

    if isinstance(v, core.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, core.Jaxpr):
        return [v]
    if isinstance(v, (list, tuple)):
        out = []
        for item in v:
            out.extend(_sub_jaxprs(item))
        return out
    return []


def iter_jaxprs(jaxpr):
    """Yield `jaxpr` and every sub-jaxpr (while/cond/scan/pjit bodies)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_jaxprs(sub)


def find_f64(closed_jaxpr) -> List[str]:
    """Descriptions of every 64-bit value in the jaxpr (empty = clean)."""
    bad: List[str] = []
    wide = ("float64", "int64", "uint64", "complex128")
    for j in iter_jaxprs(closed_jaxpr.jaxpr):
        for v in list(j.constvars) + list(j.invars) + list(j.outvars):
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and str(dt) in wide:
                bad.append(f"var {v} : {dt}")
        for eqn in j.eqns:
            for v in eqn.outvars:
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None and str(dt) in wide:
                    bad.append(f"{eqn.primitive.name} -> {dt}")
    return bad


def find_callbacks(closed_jaxpr) -> List[str]:
    """Names of callback primitives present in the jaxpr (empty = clean)."""
    found: List[str] = []
    for j in iter_jaxprs(closed_jaxpr.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name in _CALLBACK_PRIMITIVES:
                found.append(eqn.primitive.name)
    return found


# --------------------------------------------------------------------------
# audited scenes (built once per process; tiny but real — they exercise the
# stream tracer, the area light, the matte BSDF and the box film)
# --------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _stream_scene(integrator: str = "path", spp: int = 2):
    """~2.2k-triangle killeroo-like scene — big enough for the stream
    (treelet worklist) acceleration path, small enough to trace fast."""
    from tpu_pbrt.scenes import compile_api, make_killeroo_like

    api = make_killeroo_like(
        res=16, spp=spp, integrator=integrator, maxdepth=3,
        n_theta=24, n_phi=48,
    )
    return compile_api(api)


@lru_cache(maxsize=None)
def _cornell_scene(integrator: str, spp: int = 2):
    from tpu_pbrt.scenes import compile_api, make_cornell

    api = make_cornell(res=16, spp=spp, integrator=integrator, maxdepth=3)
    return compile_api(api)


@lru_cache(maxsize=None)
def _media_scene(spp: int = 2):
    """Homogeneous-fog scene for the volpath entry point (volpath's li
    requires a compiled MediumTable in dev)."""
    from tpu_pbrt.scene.api import Options, parse_string, pbrt_init
    from tpu_pbrt.scenes import compile_api

    api = pbrt_init(Options(quiet=True))
    parse_string(
        f"""
Integrator "volpath" "integer maxdepth" [3]
Sampler "zerotwosequence" "integer pixelsamples" [{spp}]
PixelFilter "box"
Film "image" "integer xresolution" [16] "integer yresolution" [16] "string filename" [""]
LookAt 0 0 -3  0 0 0  0 1 0
MakeNamedMedium "fog" "string type" "homogeneous" "rgb sigma_a" [0.05 0.05 0.05] "rgb sigma_s" [0.4 0.4 0.4] "float g" [0.0]
MediumInterface "" "fog"
Camera "perspective" "float fov" [50]
WorldBegin
AttributeBegin
AreaLightSource "diffuse" "rgb L" [8 8 8]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-1 2.9 -1  1 2.9 -1  1 2.9 1  -1 2.9 1]
AttributeEnd
Material "matte" "rgb Kd" [0.6 0.6 0.6]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3] "point P" [-4 -1 2  -4 3 2  4 3 2  4 -1 2]
""",
        api,
        render=False,
    )
    return compile_api(api)


def integrator_li_jaxpr(integrator: str = "path", scene_kind: str = "stream"):
    """Trace <integrator>'s fixed-batch li over a 64-ray wave and return
    the ClosedJaxpr — the object the f64/callback assertions run over."""
    import jax
    import jax.numpy as jnp

    if scene_kind == "media":
        scene, integ = _media_scene()
    elif scene_kind == "stream":
        scene, integ = _stream_scene(integrator)
    else:
        scene, integ = _cornell_scene(integrator)
    dev = scene.dev
    n = 64
    o = jnp.zeros((n, 3), jnp.float32)
    d = jnp.tile(jnp.asarray([0.0, 0.0, 1.0], jnp.float32), (n, 1))
    px = jnp.zeros((n,), jnp.int32)
    py = jnp.zeros((n,), jnp.int32)
    s = jnp.zeros((n,), jnp.int32)
    return jax.make_jaxpr(
        lambda o, d, px, py, s: integ.li(dev, o, d, px, py, s)
    )(o, d, px, py, s)


def pool_chunk_jaxpr(n_work: int = 256, pool: int = 64):
    """Trace the persistent-wavefront pool drain (regeneration in
    place + bounce + deposit) and return the ClosedJaxpr."""
    import jax
    import jax.numpy as jnp

    scene, integ = _stream_scene("path")
    film = scene.film

    def fn(fs, start_pix, start_s):
        return integ.pool_chunk(
            scene.dev, fs, start_pix, start_s, n_work, pool,
            film=film, cam=scene.camera,
        )

    return jax.make_jaxpr(fn)(
        film.init_state(), jnp.int32(0), jnp.int32(0)
    )


def stream_traversal_jaxpr():
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.accel.stream import stream_intersect

    scene, _ = _stream_scene("path")
    dev = scene.dev
    n = 128
    o = jnp.zeros((n, 3), jnp.float32)
    d = jnp.tile(jnp.asarray([0.0, 0.0, 1.0], jnp.float32), (n, 1))
    return jax.make_jaxpr(
        lambda o, d: stream_intersect(
            dev["tstream"], dev["tri_verts"], o, d, jnp.inf,
            tv9T=dev.get("tri_verts9T"),
        )
    )(o, d)


def film_deposit_jaxpr(pixel_path: bool = False):
    import jax
    import jax.numpy as jnp

    scene, _ = _stream_scene("path")
    film = scene.film
    n = 64
    L = jnp.zeros((n, 3), jnp.float32)
    wt = jnp.ones((n,), jnp.float32)
    if pixel_path:
        px = jnp.zeros((n,), jnp.int32)
        done = jnp.ones((n,), bool)
        return jax.make_jaxpr(
            lambda fs, px, py, L: film.add_samples_pixel(
                fs, px, py, L, done, wt
            )
        )(film.init_state(), px, px, L)
    pf = jnp.zeros((n, 2), jnp.float32)
    return jax.make_jaxpr(
        lambda fs, pf, L: film.add_samples(fs, pf, L, wt)
    )(film.init_state(), pf, L)


def sppm_pass_jaxprs():
    """Trace SPPM's two jitted passes (camera visible-point gather and
    photon trace+deposit) and return both ClosedJaxprs."""
    import jax
    import jax.numpy as jnp

    scene, integ = _cornell_scene("sppm")
    dev = scene.dev
    n = 64
    px = jnp.zeros((n,), jnp.int32)
    py = jnp.zeros((n,), jnp.int32)
    cam = jax.make_jaxpr(
        lambda px, py: integ._camera_pass(dev, px, py, 0)
    )(px, py)
    photon = jax.make_jaxpr(
        lambda: integ._photon_pass(dev, 64, 0)
    )()
    return cam, photon


def serve_step_jaxpr():
    """Trace the render service's slice-dispatch entry point (ISSUE 6):
    the ChunkPlan closure the service schedules one chunk-slice of per
    step, at a service-shaped slice width (smaller than the batch
    chunk — the preemption quantum). This is the program every serve
    dispatch runs, so the budget gate covers the serving hot path even
    with the accelerator down."""
    import jax
    import jax.numpy as jnp

    scene, integ = _stream_scene("path")
    film = scene.film
    plan = integ.prepare_chunks(scene, chunk=256)

    def fn(fs, start_pix, start_s):
        return plan.jfn(fs, scene.dev, start_pix, start_s)

    return jax.make_jaxpr(fn)(
        film.init_state(), jnp.int32(0), jnp.int32(0)
    )


def mesh_step_jaxpr():
    """Trace the sharded_pool_renderer SPMD step over a 1..n-device CPU
    mesh (the ICI film-merge psum + per-device drain)."""
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.core.film import merge_film
    from tpu_pbrt.parallel.mesh import (
        device_spread,
        make_mesh,
        sharded_pool_renderer,
        work_granule,
        work_item,
    )

    scene, integ = _stream_scene("path")
    film = scene.film
    n_dev = len(jax.devices())
    mesh = make_mesh(n_dev)
    g = work_granule(128, scene.sampler.spp, n_dev)

    def per_device_fn(dev, start):
        # the round-robin granules, the telemetry counters AND the
        # one-hot (waves, rays) spread block ride the step exactly as the
        # real render loop threads them (common.py per_device_fn), so the
        # audited program IS the dispatched one — a regression inside
        # work_item, device_spread or the counter carry must drift this
        # fingerprint and fail the budget/shardcheck gates; counters and
        # block are None (empty pytrees) under TPU_PBRT_TELEMETRY=0
        fs2, nrays, live, waves, trunc, ctr = integ.pool_chunk(
            dev, film.init_state(), start[0, 0], start[0, 1], 128, 64,
            film=film, cam=scene.camera,
            work_offset=lambda k: work_item(k, 0, n_dev, g),
        )
        spread = (
            device_spread((waves, nrays), n_dev) if ctr is not None else None
        )
        return fs2, (nrays, live, waves, trunc, ctr, spread)

    step = sharded_pool_renderer(mesh, per_device_fn)

    def fn(fs, starts):
        contrib, aux = step(scene.dev, starts)
        return merge_film(fs, contrib), aux

    starts = jnp.zeros((n_dev, 2), jnp.int32)
    return jax.make_jaxpr(fn)(film.init_state(), starts)


# --------------------------------------------------------------------------
# compile-time checks
# --------------------------------------------------------------------------


def donation_aliases(compiled_text: str) -> int:
    """Number of aliased inputs in a compiled HLO module. The
    `may-alias`/`must-alias` markers appear only inside the module's
    input_output_alias table, so a plain count is exact."""
    if "input_output_alias=" not in compiled_text:
        return 0
    return compiled_text.count("may-alias") + compiled_text.count(
        "must-alias"
    )


def check_film_donation() -> List[str]:
    """Compile the pool chunk function with the render loop's
    donate_argnums and assert every FilmState buffer is aliased
    input->output in the EXECUTABLE (not just requested)."""
    import jax
    import jax.numpy as jnp

    scene, integ = _stream_scene("path")
    film = scene.film

    def chunk_fn(fs, start_pix, start_s):
        out = integ.pool_chunk(
            scene.dev, fs, start_pix, start_s, 256, 64,
            film=film, cam=scene.camera,
        )
        return out[0]

    jfn = jax.jit(chunk_fn, donate_argnums=(0,))
    txt = (
        jfn.lower(film.init_state(), jnp.int32(0), jnp.int32(0))
        .compile()
        .as_text()
    )
    n_leaves = len(jax.tree.leaves(film.init_state()))
    n_alias = donation_aliases(txt)
    if n_alias < n_leaves:
        return [
            f"film donation not materialized: {n_alias} aliased buffers "
            f"in the executable, expected >= {n_leaves} (FilmState leaves)"
        ]
    return []


def check_recompile_guard() -> List[str]:
    """Render two same-shape waves through the real render loop and
    assert the jit cache did not grow — retraces in the chunk loop
    would pay compile time per chunk instead of per scene."""
    scene, integ = _stream_scene("path")
    integ.render(scene)
    jfn = integ._jit_cache[1]
    size_after_first = jfn._cache_size()
    integ.render(scene)
    jfn2 = integ._jit_cache[1]
    fails = []
    if jfn2 is not jfn:
        fails.append("second same-shape render rebuilt the chunk closure")
    if jfn2._cache_size() > size_after_first:
        fails.append(
            f"jit cache grew across same-shape renders "
            f"({size_after_first} -> {jfn2._cache_size()})"
        )
    if size_after_first > 1:
        fails.append(
            f"first render traced {size_after_first} chunk variants "
            "(expected one executable for the whole wave loop)"
        )
    return fails


def check_transfer_guard() -> List[str]:
    """Smoke render under jax.transfer_guard('disallow'): every implicit
    host<->device transfer in the render loop is a hard error."""
    import jax

    scene, integ = _stream_scene("path", spp=1)
    try:
        with jax.transfer_guard("disallow"):
            res = integ.render(scene)
    except Exception as e:
        # only a guard trip is THIS finding; anything else (capacity
        # audit, OOM, ...) must be reported as its own crash, not as a
        # phantom host sync
        if "transfer" in str(e).lower():
            return [f"implicit transfer in the render loop: {e}"]
        raise
    img = np.asarray(res.image, np.float32)
    if not np.isfinite(img).all():
        return ["smoke render under transfer_guard produced non-finite pixels"]
    return []


# --------------------------------------------------------------------------
# suite driver
# --------------------------------------------------------------------------


def _jaxpr_invariants(name: str, closed_jaxpr) -> List[str]:
    fails = []
    f64 = find_f64(closed_jaxpr)
    if f64:
        fails.append(f"{name}: f64 in jaxpr ({f64[0]}; {len(f64)} total)")
    cbs = find_callbacks(closed_jaxpr)
    if cbs:
        fails.append(f"{name}: callback primitives {sorted(set(cbs))}")
    return fails


def run_audit(include_compile: bool = True) -> List[str]:
    """Run every audit; returns failure strings (empty = all invariants
    hold). Exceptions are reported as failures, not raised — the CLI
    must always print a complete report."""
    failures: List[str] = []
    checks = [
        ("path.li jaxpr", lambda: _jaxpr_invariants(
            "path.li", integrator_li_jaxpr("path"))),
        ("pool_chunk jaxpr", lambda: _jaxpr_invariants(
            "pool_chunk", pool_chunk_jaxpr())),
        ("stream traversal jaxpr", lambda: _jaxpr_invariants(
            "stream_intersect", stream_traversal_jaxpr())),
        ("film deposit jaxpr", lambda: _jaxpr_invariants(
            "film.add_samples", film_deposit_jaxpr())),
        ("film pixel-deposit jaxpr", lambda: _jaxpr_invariants(
            "film.add_samples_pixel", film_deposit_jaxpr(pixel_path=True))),
        ("mesh step jaxpr", lambda: _jaxpr_invariants(
            "sharded_pool_renderer", mesh_step_jaxpr())),
        ("serve step jaxpr", lambda: _jaxpr_invariants(
            "serve_step", serve_step_jaxpr())),
    ]
    if include_compile:
        checks += [
            ("film donation", check_film_donation),
            ("recompile guard", check_recompile_guard),
            ("transfer guard", check_transfer_guard),
        ]
    for label, fn in checks:
        try:
            failures.extend(fn())
        except Exception as e:  # noqa: BLE001
            failures.append(f"{label}: audit crashed: {type(e).__name__}: {e}")
    return failures
