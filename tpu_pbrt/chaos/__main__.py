"""`python -m tpu_pbrt.chaos` — the deterministic recovery matrix.

Renders the small cornell scene on CPU once undisturbed, then replays it
under every chaos scenario — poisoned dispatch, clean re-dispatch, torn /
crashed / bit-flipped checkpoint writes, corrupt-checkpoint resume, NaN
wave, retry-budget exhaustion, mesh device loss — asserting that each
recovery converges to a final film **bit-identical** to the undisturbed
render (chunks are idempotent pure functions of the work range and the
counter-based RNG is replay-exact, so recovery is EXACT, not
approximate). The one deliberate exception is `nan-wave-scrub`, which
validates the DEGRADE semantics instead: the firewall zeroes the
contaminated deposits, the final image stays fully finite, and
`nonfinite_deposits > 0` is reported in telemetry.

The matrix is also the health watchdog's truth table (ISSUE 15): the
`serve-wedge` and `serve-backoff-storm` rows inject serve drains the
watchdog MUST flag, and every other (clean) row asserts it stays
silent — a false-positive gate run after each pass.

The fleet rows (ISSUE 20) extend the ladder across replicas: `fleet-
replica-kill` kills a serve replica mid-job and asserts the job
resumes on the survivor from the durable spool bit-identically, and
`fleet-router-restart` restarts the ROUTER, adopts the same replicas
from their `stats` verbs, and drains every job to the same bits.

This is the SURVEY §2e fault-tolerance claim turned into a gate: it runs
in tools/ci.sh after the telemetry smoke stage, with no accelerator
required.

    python -m tpu_pbrt.chaos            # full matrix
    python -m tpu_pbrt.chaos --list     # scenario names
    python -m tpu_pbrt.chaos --only torn-ckpt-fallback,nan-wave-scrub
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

# matrix workload: small enough to compile fast at opt level 0, big
# enough for 8 chunks (the recovery ladder needs chunk structure)
RES = int(os.environ.get("CHAOS_RES", "20"))
SPP = int(os.environ.get("CHAOS_SPP", "4"))
MAXDEPTH = 3
N_CHUNKS = 8
CHUNK = RES * RES * SPP // N_CHUNKS

#: cached undisturbed renders (film arrays + ray count), keyed by mesh size
_REFS = {}


def _setup_env():
    """Process env for a standalone `python -m tpu_pbrt.chaos` run —
    BEFORE jax/tpu_pbrt import: CPU backend, virtual 8-device mesh, fast
    XLA pipeline (test renders are tiny; LLVM optimization is the cost),
    snappy deterministic retry backoff."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    if "xla_backend_optimization_level" not in flags:
        flags += " --xla_backend_optimization_level=0"
    os.environ["XLA_FLAGS"] = flags
    os.environ.setdefault("JAX_ENABLE_X64", "0")


@contextlib.contextmanager
def _env(**overrides):
    """Set TPU_PBRT_* knobs for one scenario and resync the config
    snapshot (the same seam tests/conftest.py uses — the matrix is test
    tooling, not production code)."""
    from tpu_pbrt import config

    old = {k: os.environ.get(k) for k in overrides}
    os.environ.update({k: str(v) for k, v in overrides.items()})
    config.reload()
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        config.reload()


def _fresh():
    from tpu_pbrt.scenes import compile_api, make_cornell

    api = make_cornell(
        res=RES, spp=SPP, integrator="path", maxdepth=MAXDEPTH
    )
    return compile_api(api)


def _fresh_stream():
    """A killeroo-like scene: above 256 triangles, so the stream tracer
    (the matrix's cornell box compiles to the brute path)."""
    from tpu_pbrt.scenes import compile_api, make_killeroo_like

    scene, integ = compile_api(make_killeroo_like(
        res=16, spp=2, integrator="path", maxdepth=MAXDEPTH,
        n_theta=24, n_phi=48,
    ))
    if "tstream" not in scene.dev:
        raise RuntimeError("scene is not stream-traced")
    return scene, integ


def _film(result):
    import jax
    import numpy as np

    st = jax.device_get(result.film_state)
    return [
        np.asarray(st.rgb), np.asarray(st.weight), np.asarray(st.splat)
    ]


def _identical(a, b) -> bool:
    import numpy as np

    return all(np.array_equal(x, y) for x, y in zip(a, b))


def _run(plan=None, seed=0, ckpt=None, ckpt_every=1, mesh_n=0, env=None,
         fresh=_fresh):
    """One render of `fresh()`'s scene under a chaos plan. Returns
    (result_or_exception, CHAOS fired report). The registry is always
    cleared afterwards."""
    from tpu_pbrt.chaos import CHAOS

    overrides = {
        "TPU_PBRT_CHUNK": CHUNK,
        "TPU_PBRT_RETRY_BACKOFF": os.environ.get(
            "TPU_PBRT_RETRY_BACKOFF", "0.01"
        ),
    }
    overrides.update(env or {})
    with _env(**overrides):
        if plan:
            CHAOS.install(plan, seed=seed)
        try:
            scene, integ = fresh()
            kw = {}
            if ckpt:
                kw = dict(checkpoint_path=ckpt, checkpoint_every=ckpt_every)
            if mesh_n:
                from tpu_pbrt.parallel.mesh import make_mesh

                out = integ.render(scene, mesh=make_mesh(mesh_n), **kw)
            else:
                out = integ.render(scene, **kw)
        except Exception as e:  # noqa: BLE001 — scenario asserts on it
            out = e
        finally:
            rep = CHAOS.report()
            CHAOS.clear()
    return out, rep


def _reference(mesh_n=0):
    if mesh_n not in _REFS:
        r, _ = _run(mesh_n=mesh_n)
        if isinstance(r, Exception):
            raise r
        _REFS[mesh_n] = (_film(r), r.rays_traced)
    return _REFS[mesh_n]


def _check_recovered(r, rep, *, mesh_n=0, want_fired=None) -> tuple:
    """Shared postcondition: every fault fired the expected number of
    times and the final film is bit-identical to the undisturbed one."""
    if isinstance(r, Exception):
        return False, f"render raised {type(r).__name__}: {r}"
    fired = {e["fault"]: e["fired"] for e in rep}
    for spec, want in (want_fired or {}).items():
        got = next(
            (v for k, v in fired.items() if k.startswith(spec)), None
        )
        if got != want:
            return False, f"fault {spec} fired {got}, wanted {want}"
    ref_film, ref_rays = _reference(mesh_n)
    if not _identical(_film(r), ref_film):
        return False, "final film NOT bit-identical to undisturbed render"
    if r.rays_traced != ref_rays:
        return False, f"rays_traced {r.rays_traced} != {ref_rays}"
    return True, f"bit-identical; fired={fired}"


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------


def scen_clean_redispatch(tmp):
    """A chunk dispatch dies WITHOUT touching the film (worker loss
    before the dispatch ran): plain re-dispatch is exact."""
    r, rep = _run(plan="dispatch:fail@chunk=1")
    return _check_recovered(r, rep, want_fired={"dispatch:fail": 1})


def scen_poison_rollback(tmp):
    """A mid-dispatch loss poisons the film accumulator: roll back to
    the last durable checkpoint and replay."""
    r, rep = _run(
        plan="dispatch:poison@chunk=3",
        ckpt=os.path.join(tmp, "film.ckpt"),
    )
    ok, detail = _check_recovered(r, rep, want_fired={"dispatch:poison": 1})
    if ok and r.stats.get("recovery", {}).get("rollbacks") != 1:
        return False, "expected exactly 1 checkpoint rollback"
    return ok, detail


def scen_poison_restart(tmp):
    """Poisoning failure with NO checkpoint configured: the only safe
    recovery is a from-scratch restart — still exact."""
    r, rep = _run(plan="dispatch:poison@chunk=2")
    ok, detail = _check_recovered(r, rep, want_fired={"dispatch:poison": 1})
    if ok and r.stats.get("recovery", {}).get("restarts") != 1:
        return False, "expected exactly 1 restart"
    return ok, detail


def scen_torn_ckpt_fallback(tmp):
    """Checkpoint write 3 publishes a TORN file; the poisoning failure
    that follows must fall back to the rotated .prev and still recover
    exactly."""
    r, rep = _run(
        plan="ckpt:torn@write=3,dispatch:poison@chunk=3",
        ckpt=os.path.join(tmp, "film.ckpt"),
    )
    return _check_recovered(
        r, rep, want_fired={"ckpt:torn": 1, "dispatch:poison": 1}
    )


def scen_crash_ckpt_write(tmp):
    """Simulated crash between the tmp write and the rename: the write
    simply never happened; recovery uses the previous durable file."""
    r, rep = _run(
        plan="ckpt:crash@write=3,dispatch:poison@chunk=3",
        ckpt=os.path.join(tmp, "film.ckpt"),
    )
    return _check_recovered(
        r, rep, want_fired={"ckpt:crash": 1, "dispatch:poison": 1}
    )


def scen_bitflip_ckpt_fallback(tmp):
    """A bit-flipped checkpoint fails the v4 content checksum at load;
    rollback falls back to .prev."""
    r, rep = _run(
        plan="ckpt:bitflip@write=3,dispatch:poison@chunk=3",
        ckpt=os.path.join(tmp, "film.ckpt"),
    )
    return _check_recovered(
        r, rep, want_fired={"ckpt:bitflip": 1, "dispatch:poison": 1}
    )


def scen_nan_wave_retry(tmp):
    """A NaN wave under TPU_PBRT_NONFINITE=retry: the firewall detects
    the scrubbed deposits at the chunk boundary, the chunk is treated as
    poisoned and re-rendered clean — recovery is EXACT."""
    r, rep = _run(
        plan="nan:wave@1&chunk=1",
        ckpt=os.path.join(tmp, "film.ckpt"),
        env={"TPU_PBRT_NONFINITE": "retry"},
    )
    ok, detail = _check_recovered(r, rep, want_fired={"nan:wave": 1})
    if ok and r.stats.get("recovery", {}).get("nonfinite_retries") != 1:
        return False, "expected exactly 1 firewall retry"
    return ok, detail


def scen_nan_wave_scrub(tmp):
    """A NaN wave under the DEFAULT scrub mode: degrade, don't die — the
    final image is fully finite and the contamination is counted in
    nonfinite_deposits (the acceptance telemetry signal). Deliberately
    NOT bit-identical: the scrubbed samples deposited zero."""
    import numpy as np

    r, rep = _run(plan="nan:wave@1&chunk=1")
    if isinstance(r, Exception):
        return False, f"render raised {type(r).__name__}: {r}"
    fired = sum(e["fired"] for e in rep)
    if fired != 1:
        return False, f"nan fault fired {fired} times, wanted 1"
    img = np.asarray(r.image)
    if not np.isfinite(img).all():
        return False, "final image carries non-finite pixels"
    nf = (
        r.stats.get("telemetry", {})
        .get("counters", {})
        .get("nonfinite_deposits", 0)
    )
    if not nf > 0:
        return False, f"nonfinite_deposits = {nf}, wanted > 0"
    return True, f"image finite; nonfinite_deposits={nf}"


def _run_exhaustion(tmp):
    """Shared phase 1 for the exhaustion scenarios: chunk 5 fails every
    attempt, the retry budget (2) exhausts, and the loop writes an
    emergency checkpoint before raising."""
    ck = os.path.join(tmp, "film.ckpt")
    r, rep = _run(
        plan="dispatch:fail@chunk=5&times=99",
        ckpt=ck,
        env={"TPU_PBRT_RETRY_MAX": "2"},
    )
    if not isinstance(r, RuntimeError):
        return ck, f"expected RuntimeError, got {type(r).__name__}"
    from tpu_pbrt.parallel.checkpoint import load_checkpoint

    _, cursor, _, _ = load_checkpoint(ck)
    if cursor != 5:
        return ck, f"emergency checkpoint cursor {cursor}, wanted 5"
    return ck, None


def scen_exhaustion_emergency_resume(tmp):
    """Retry-budget exhaustion: the render dies loudly, but the
    emergency checkpoint preserves every completed chunk — a later
    resume finishes the job bit-identically."""
    ck, err = _run_exhaustion(tmp)
    if err:
        return False, err
    r2, rep2 = _run(ckpt=ck)  # no plan: the infra 'recovered'
    return _check_recovered(r2, rep2)


def scen_corrupt_resume(tmp):
    """Corrupt-checkpoint resume: the current checkpoint file is
    bit-flipped ON DISK after the crash; the resume must fall back to
    .prev and re-render the missing chunks exactly."""
    ck, err = _run_exhaustion(tmp)
    if err:
        return False, err
    size = os.path.getsize(ck)
    with open(ck, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))
    r2, rep2 = _run(ckpt=ck)
    return _check_recovered(r2, rep2)


def scen_mesh_device_loss(tmp):
    """Single-device loss in the mesh drain (simulated: the whole SPMD
    dispatch fails as state-poisoning — see parallel/mesh.py's failure
    model): rollback + re-dispatch on the virtual CPU mesh recovers
    bit-identically to the undisturbed MESH render."""
    import jax

    if len(jax.devices()) < 4:
        return True, "SKIP: needs >= 4 devices"
    r, rep = _run(
        plan="mesh:lost@chunk=1",
        ckpt=os.path.join(tmp, "film.ckpt"),
        mesh_n=4,
    )
    return _check_recovered(
        r, rep, mesh_n=4, want_fired={"mesh:lost": 1}
    )


def scen_stream_tracer(tmp):
    """The recovery ladder over a STREAM-traced scene: a poisoning
    dispatch loss mid-render (no checkpoint, so a from-scratch restart)
    must recover to a film bit-identical to the clean render. No other
    row runs the ladder over the stream tracer."""
    ref, _ = _run(fresh=_fresh_stream)
    r, rep = _run(plan="dispatch:poison@chunk=1", fresh=_fresh_stream)
    for out in (ref, r):
        if isinstance(out, Exception):
            return False, f"render raised {type(out).__name__}: {out}"
    fired = {e["fault"]: e["fired"] for e in rep}
    if sum(fired.values()) != 1:
        return False, f"dispatch fault fired {fired}, wanted 1"
    if r.stats.get("recovery", {}).get("restarts") != 1:
        return False, "expected exactly 1 restart"
    if not _identical(_film(r), _film(ref)):
        return False, "recovered film NOT bit-identical to clean render"
    if r.rays_traced != ref.rays_traced:
        return False, f"rays {r.rays_traced} != {ref.rays_traced}"
    return True, f"bit-identical; fired={fired}"


def scen_pipeline(tmp):
    """Async pipelined dispatch (ISSUE 13): a poisoning dispatch loss
    with TPU_PBRT_PIPELINE=3 slices in flight — the window is flushed,
    the loop rolls back to the last durable checkpoint (whose cadence
    writes were DEFERRED under in-flight compute via the film
    snapshot) and the recovered film is bit-identical to the
    undisturbed render. Pins the tentpole's two contracts at once:
    depth-N == depth-1 bits, and the recovery ladder carrying over
    unchanged with a non-empty window."""
    r, rep = _run(
        plan="dispatch:poison@chunk=3",
        ckpt=os.path.join(tmp, "film.ckpt"),
        env={"TPU_PBRT_PIPELINE": "3"},
    )
    ok, detail = _check_recovered(r, rep, want_fired={"dispatch:poison": 1})
    if ok and r.stats.get("recovery", {}).get("rollbacks") != 1:
        return False, "expected exactly 1 checkpoint rollback"
    return ok, detail


def _serve_retry_storm(steps, env):
    """Shared rig for the watchdog rows: a serve job whose chunk-0
    dispatch fails EVERY attempt (times=99) with zero retry backoff and
    an unreachable retry budget — `steps` scheduler steps of pure
    no-progress retrying, then the health verdict. Returns (service,
    HealthReport) evaluated INSIDE the env overrides."""
    from tpu_pbrt.chaos import CHAOS
    from tpu_pbrt.obs.health import evaluate
    from tpu_pbrt.obs.metrics import METRICS

    overrides = {
        "TPU_PBRT_CHUNK": CHUNK,
        "TPU_PBRT_RETRY_BACKOFF": "0",
        "TPU_PBRT_RETRY_MAX": "999",
    }
    overrides.update(env or {})
    with _env(**overrides):
        from tpu_pbrt.serve.service import RenderService

        METRICS.reset()
        scene, integ = _fresh()
        service = RenderService(quiet=True)
        service.submit(compiled=(scene, integ), tenant="chaos")
        CHAOS.install("dispatch:fail@chunk=0&times=99", seed=0)
        try:
            for _ in range(steps):
                service.step()
            rep = evaluate(service)
        finally:
            CHAOS.clear()
            METRICS.reset()
    return service, rep


def scen_serve_wedge(tmp):
    """Health-watchdog row (ISSUE 15): a serve drain that retries the
    same chunk forever — runnable work, K+ step() calls, no cursor
    advance — MUST flag `wedge` (the failure mode that previously only
    surfaced as a client timeout)."""
    from tpu_pbrt.obs.health import Thresholds

    k = Thresholds().resolved_wedge_steps()
    service, rep = _serve_retry_storm(steps=k + 2, env=None)
    if service.last_progress_step != 0:
        return False, "rig broke: the wedged job made progress"
    if "wedge" not in rep.firing():
        return False, f"wedge NOT flagged after {k + 2} stuck steps: {rep.to_dict()}"
    return True, f"flagged {rep.firing()} after {k + 2} stuck steps"


def scen_serve_backoff_storm(tmp):
    """Health-watchdog row: the SAME retry streak caught EARLY — enough
    steps for the job's live attempt counter to cross the storm
    threshold, but well inside the wedge window. `backoff_storm` must
    flag; `wedge` must NOT (the two conditions separate a hot retry
    loop from a dead drain)."""
    from tpu_pbrt.obs.health import Thresholds

    th = Thresholds()
    steps = th.storm_attempts + 1
    if steps >= th.resolved_wedge_steps():
        return False, "rig broke: storm window not inside wedge window"
    service, rep = _serve_retry_storm(steps=steps, env=None)
    job = next(iter(service.jobs.values()))
    if job.attempt < th.storm_attempts:
        return False, f"rig broke: attempt {job.attempt} under threshold"
    if "backoff_storm" not in rep.firing():
        return False, f"backoff_storm NOT flagged: {rep.to_dict()}"
    if "wedge" in rep.firing():
        return False, f"wedge flagged {steps} steps in (threshold "  \
            f"{th.resolved_wedge_steps()}): {rep.to_dict()}"
    return True, f"flagged {rep.firing()} at attempt {job.attempt}"


def _fleet_rig(tmp):
    """Shared rig for the fleet rows: two real in-process replicas under
    one VirtualClock behind a FleetRouter, matrix chunking on both sides
    so the failover resume replays the exact chunk boundaries the
    undisturbed reference used."""
    from tpu_pbrt.fleet.router import FleetRouter, LocalReplica
    from tpu_pbrt.utils.clock import VirtualClock

    clock = VirtualClock(start=0.0, tick=1e-6)
    fleet = [
        LocalReplica(
            rid, clock=clock, chunk=CHUNK,
            spool_dir=os.path.join(tmp, rid),
        )
        for rid in ("r0", "r1")
    ]
    router = FleetRouter(
        fleet, clock=clock, spool_dir=os.path.join(tmp, "fleet"),
    )
    return clock, fleet, router


def scen_fleet_replica_kill(tmp):
    """Fleet failover row (ISSUE 20): a replica is KILLED mid-job past a
    durable checkpoint; the router fails the job over to the survivor,
    which resumes from the spool — the final film must be bit-identical
    to the undisturbed render (chunks are idempotent, the cursor is
    durable, and film accumulation from the cursor is sequential)."""
    from tpu_pbrt.obs.metrics import METRICS
    from tpu_pbrt.serve.service import DONE

    with _env(TPU_PBRT_CHUNK=CHUNK, TPU_PBRT_RETRY_BACKOFF="0.01"):
        METRICS.reset()
        _, _, router = _fleet_rig(tmp)
        try:
            scene, integ = _fresh()
            job = router.submit(
                compiled=(scene, integ), resident_key="chaos:cornell",
                checkpoint_every=1, tenant="chaos",
            )
            victim = router.owner(job)
            survivor = "r1" if victim == "r0" else "r0"
            for _ in range(4 * N_CHUNKS):
                if router.poll(job)["chunks_done"] >= 2:
                    break
                if router.step() is None:
                    return False, "no progress before the kill"
            else:
                return False, "never reached chunk 2 before the kill"
            at_kill = router.poll(job)["chunks_done"]
            moved = router.kill_replica(victim)
            if moved != [job]:
                return False, f"failover moved {moved}, wanted [{job!r}]"
            if router.owner(job) != survivor:
                return False, (
                    f"{job} on {router.owner(job)}, wanted {survivor}"
                )
            router.drain_fleet()
            p = router.poll(job)
            if p["status"] != DONE:
                return False, f"job ended {p['status']!r} after failover"
            r = router.result(job)
        finally:
            METRICS.reset()
    ref_film, _ = _reference()
    if not _identical(_film(r), ref_film):
        return False, (
            "failover film NOT bit-identical to undisturbed render"
        )
    return True, (
        f"bit-identical after kill({victim})->resume({survivor}) "
        f"at chunk {at_kill} ({p['failovers']} failover)"
    )


def scen_fleet_router_restart(tmp):
    """Fleet restart row (ISSUE 20): the ROUTER dies between decisions
    and a fresh one adopts the same replicas, rebuilding its routing
    table from each replica's `stats` verb — no job is lost, the drain
    completes every adopted job, and the films stay bit-identical."""
    from tpu_pbrt.fleet.router import FleetRouter
    from tpu_pbrt.obs.metrics import METRICS
    from tpu_pbrt.serve.service import DONE

    with _env(TPU_PBRT_CHUNK=CHUNK, TPU_PBRT_RETRY_BACKOFF="0.01"):
        METRICS.reset()
        clock, fleet, router = _fleet_rig(tmp)
        try:
            scene, integ = _fresh()
            jobs = [
                router.submit(
                    compiled=(scene, integ),
                    resident_key=f"chaos:cornell{i}",
                    checkpoint_every=1, tenant="chaos",
                )
                for i in range(2)
            ]
            for _ in range(3):  # some mid-flight progress, then "crash"
                router.step()
            router2 = FleetRouter.adopt(
                fleet, clock=clock,
                spool_dir=os.path.join(tmp, "fleet"),
            )
            lost = [j for j in jobs if j not in router2.jobs]
            if lost:
                return False, f"adopt lost job(s): {lost}"
            for j in jobs:
                if router2.owner(j) != router.owner(j):
                    return False, (
                        f"adopt re-homed {j}: {router.owner(j)} -> "
                        f"{router2.owner(j)}"
                    )
            router2.drain_fleet()
            polls = {j: router2.poll(j) for j in jobs}
            bad = {j: p["status"] for j, p in polls.items()
                   if p["status"] != DONE}
            if bad:
                return False, f"adopted job(s) did not finish: {bad}"
            films = [_film(router2.result(j)) for j in jobs]
        finally:
            METRICS.reset()
    ref_film, _ = _reference()
    for j, film in zip(jobs, films):
        if not _identical(film, ref_film):
            return False, f"{j}: film NOT bit-identical after restart"
    return True, (
        f"{len(jobs)} job(s) adopted across a router restart, "
        "all bit-identical"
    )


SCENARIOS = {
    "stream-tracer": scen_stream_tracer,
    "pipeline": scen_pipeline,
    "clean-redispatch": scen_clean_redispatch,
    "poison-rollback": scen_poison_rollback,
    "poison-restart": scen_poison_restart,
    "torn-ckpt-fallback": scen_torn_ckpt_fallback,
    "crash-ckpt-write": scen_crash_ckpt_write,
    "bitflip-ckpt-fallback": scen_bitflip_ckpt_fallback,
    "nan-wave-retry": scen_nan_wave_retry,
    "nan-wave-scrub": scen_nan_wave_scrub,
    "exhaustion-emergency-resume": scen_exhaustion_emergency_resume,
    "corrupt-resume": scen_corrupt_resume,
    "mesh-device-loss": scen_mesh_device_loss,
    "serve-wedge": scen_serve_wedge,
    "serve-backoff-storm": scen_serve_backoff_storm,
    "fleet-replica-kill": scen_fleet_replica_kill,
    "fleet-router-restart": scen_fleet_router_restart,
}

#: rows whose whole POINT is to trip the watchdog — every other row
#: must leave the registry-derived health conditions clean (the
#: watchdog's false-positive gate over the recovery matrix)
_WATCHDOG_ROWS = {"serve-wedge", "serve-backoff-storm"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tpu_pbrt.chaos")
    ap.add_argument("--list", action="store_true", help="list scenarios")
    ap.add_argument(
        "--only", default="",
        help="comma-separated subset of scenario names to run",
    )
    args = ap.parse_args(argv)
    if args.list:
        for name, fn in SCENARIOS.items():
            print(f"{name}: {' '.join((fn.__doc__ or '').split())}")
        return 0

    _setup_env()
    import tempfile

    from tpu_pbrt.config import place_compile_cache

    place_compile_cache()

    only = {s for s in args.only.split(",") if s}
    unknown = only - set(SCENARIOS)
    if unknown:
        ap.error(f"unknown scenario(s): {sorted(unknown)}")
    failed = []
    ran = 0
    t_all = time.time()
    with tempfile.TemporaryDirectory() as tmp:
        for name, fn in SCENARIOS.items():
            if only and name not in only:
                continue
            ran += 1
            sdir = os.path.join(tmp, name)
            os.makedirs(sdir, exist_ok=True)
            t0 = time.time()
            try:
                ok, detail = fn(sdir)
            except Exception as e:  # noqa: BLE001 — a broken scenario is a FAIL
                ok, detail = False, f"{type(e).__name__}: {e}"
            if ok and name not in _WATCHDOG_ROWS:
                # false-positive gate: a CLEAN recovery row must not
                # trip the registry-derived health conditions
                from tpu_pbrt.obs.health import evaluate

                hrep = evaluate(None)
                if not hrep.ok:
                    ok, detail = False, (
                        f"health watchdog fired on a clean row: "
                        f"{hrep.firing()}"
                    )
            dt = time.time() - t0
            print(
                f"chaos {name}: {'PASS' if ok else 'FAIL'} "
                f"({detail}) [{dt:.1f}s]",
                flush=True,
            )
            if not ok:
                failed.append(name)
    print(
        json.dumps(
            {
                "chaos_matrix": {
                    "scenarios": ran,
                    "passed": ran - len(failed),
                    "failed": failed,
                    "seconds": round(time.time() - t_all, 1),
                }
            }
        )
    )
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
