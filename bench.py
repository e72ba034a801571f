#!/usr/bin/env python
"""Benchmark driver: renders the killeroo-simple-class workload and prints
one JSON line {"metric", "value", "unit", "vs_baseline", ...}.

The workload mirrors BASELINE.json's killeroo-simple config (PathIntegrator,
matte trimesh, area light) with a procedural ~128k-triangle mesh standing in
for the PLY (pbrt-v3-scenes is not available in this environment).

Metrics (the judged pair, BASELINE.json `metric`):
- Mray/s: rays actually traced / steady-state wall time, counted in-kernel.
  A warmup pass excludes XLA compilation from the timing, matching how the
  reference's numbers would exclude its BVH build.
- mse: per-pixel MSE of an accelerator render vs the cached CPU reference
  image (tools/make_reference.py; refimg/). Target <= 1e-4.

Every phase is wall-clock budgeted (the render loop's max_seconds stops
at a chunk boundary; Mray/s divides rays actually traced by wall time, so
a partial run still measures steady-state throughput), MSE is attempted
only if the remaining budget predicts it will finish, any exception
prints a parseable JSON line, and SIGTERM reports the last completed
measurement instead of dying silently (VERDICT r2 #2). The line is for
the post-mortem; the EXIT CODE is the verdict: a run in which the backend
was unreachable, a leg raised, or the image came out black exits
non-zero.

Env knobs: BENCH_SPP/BENCH_RES (throughput run), BENCH_BUDGET_S (total
wall-clock budget, default 420), MSE_RES/MSE_SPP/REF_SPP (accuracy run),
BENCH_SKIP_MSE=1 to skip the accuracy half.

Telemetry (ISSUE 4): every phase heartbeats into the flight recorder
(TPU_PBRT_FLIGHT_PATH, default BENCH_flight.jsonl) so an outage capture
carries its phase timeline, probe retry/wait accounting and the last
counter snapshot; `--trace out.json` (or TPU_PBRT_TRACE_PATH) exports a
Chrome-trace/Perfetto span timeline; the measured JSON line gains a
`telemetry` block — device counters, per-device wave-count spread, and
the live-vs-static roofline ratio (obs/rooflive.py) next to the static
fields.
"""

import json
import os
import subprocess
import sys
import time
import zlib

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

T_START = time.time()
BUDGET = float(os.environ.get("BENCH_BUDGET_S", "520"))

# -- import-free flight heartbeats for the probe/outage phases -------------
# The probe exists because an in-process accelerator-runtime import can
# hang unboundedly; importing tpu_pbrt (whose package __init__ pulls jax)
# before the probe succeeds would reintroduce exactly that hang. These
# few lines mirror tpu_pbrt/obs/flight.py's JSONL format with ZERO
# tpu_pbrt/jax imports; once the probe passes, the real FlightRecorder
# takes over appending to the same file.
_FLIGHT_PATH = os.environ.get("TPU_PBRT_FLIGHT_PATH") or "BENCH_flight.jsonl"
_TELEMETRY_ON = os.environ.get("TPU_PBRT_TELEMETRY", "1").strip().lower() \
    not in ("0", "false", "no", "off")
_last_phase = None


def _flight_heartbeat(phase: str, **fields):
    global _last_phase
    _last_phase = phase
    if not _TELEMETRY_ON:
        return
    line = {"t": round(time.time(), 3),
            "elapsed_s": round(time.time() - T_START, 3), "phase": phase}
    line.update(fields)
    try:
        with open(_FLIGHT_PATH, "a") as f:
            f.write(json.dumps(line) + "\n")
    except OSError:
        pass


def _probe_hang_attempts() -> set:
    """Chaos seam for the probe, parsed IMPORT-FREE: `probe:hang@attempt=N`
    entries of TPU_PBRT_FAULTS name the probe attempts that must simulate
    the r4/r5-class runtime hang. This mirrors tpu_pbrt/chaos's grammar
    for the one site that runs before tpu_pbrt may be imported (the real
    registry lives behind the jax import this path must avoid)."""
    out = set()
    for entry in os.environ.get("TPU_PBRT_FAULTS", "").split(","):
        entry = entry.strip()
        if not entry.startswith("probe:hang"):
            continue
        attempt = 1
        _, _, tail = entry.partition("@")
        for part in tail.split("&"):
            part = part.strip()
            if not part:
                continue
            k, eq, v = part.partition("=")
            if not eq:
                k, v = "attempt", k  # bare value -> the site default key
            if k == "attempt":
                try:
                    attempt = int(v)
                except ValueError:
                    pass
        out.add(attempt)
    return out


#: cumulative backoff the probe slept (reported on the outage JSON line)
_PROBE_BACKOFF_S = 0.0


def probe_backend(
    timeout_s: float = 150.0, max_attempts: int = 0,
    backoff_base_s: float = 5.0, backoff_cap_s: float = 60.0,
) -> tuple[bool, str, int, float]:
    """Bounded accelerator-backend health check in a SUBPROCESS: an
    in-process jax.devices() can hang when the accelerator runtime does,
    and nothing in-process can bound it, so this function imports
    NOTHING that imports jax. The child exits before the parent touches
    jax, which also keeps to one process per chip. Returns (ok, detail,
    retries, wait_seconds): retries = probe attempts beyond the first,
    wait_seconds = total time burned in the probe incl. backoff.

    Retry policy (ISSUE 5 satellite): capped exponential backoff with
    deterministic jitter between attempts (min(base * 2^k, cap) scaled
    into [0.5, 1.0]); every attempt and every backoff is heartbeat into
    the flight recorder with its detail and the cumulative backoff, and
    an attempt is skipped rather than started when the remaining BENCH
    budget cannot absorb it. A backend that stays unreachable is then
    classified distinctly, so the line says so and not 'tracer broke'."""
    global _PROBE_BACKOFF_S
    code_ok = (
        "import jax; d = jax.devices(); "
        "print(d[0].platform, len(d), flush=True)"
    )
    # chaos probe:hang — a subprocess that outlives the timeout is
    # indistinguishable from the real hung-runtime import
    code_hang = "import time; time.sleep(3600)"
    hang_attempts = _probe_hang_attempts()
    max_attempts = max_attempts or int(
        os.environ.get("BENCH_PROBE_ATTEMPTS", "3")
    )
    t_probe = time.time()
    retries = 0
    detail = "?"
    for attempt in range(1, max_attempts + 1):
        if attempt > 1:
            retries += 1
        simulated = attempt in hang_attempts
        _flight_heartbeat(
            "probe", attempt=attempt,
            **({"chaos_hang": True} if simulated else {}),
        )
        try:
            r = subprocess.run(
                [sys.executable, "-c", code_hang if simulated else code_ok],
                capture_output=True, text=True, timeout=timeout_s,
            )
            if r.returncode == 0 and r.stdout.strip():
                detail = r.stdout.strip()
                _flight_heartbeat("probe", attempt=attempt, ok=True,
                                  backend=detail)
                return True, detail, retries, time.time() - t_probe
            detail = (r.stderr or "").strip().splitlines()[-1:] or ["?"]
            detail = f"rc={r.returncode}: {detail[0][:200]}"
        except subprocess.TimeoutExpired:
            detail = f"backend init hung >{timeout_s:.0f}s"
        _flight_heartbeat("probe", attempt=attempt, ok=False, detail=detail)
        if attempt == max_attempts:
            break
        b = min(backoff_base_s * (2.0 ** (attempt - 1)), backoff_cap_s)
        # deterministic jitter (zlib.crc32 of the attempt index): the
        # same run shape replays identically under chaos
        frac = (zlib.crc32(f"probe:{attempt}".encode()) & 0xFFFF) / 65535.0
        sleep_s = b * (0.5 + 0.5 * frac)
        if BUDGET - (time.time() - T_START) < timeout_s + sleep_s + 30:
            # no budget for another attempt + its backoff: stop probing
            # and let the outage line report what we know
            _flight_heartbeat(
                "probe_giveup", attempt=attempt,
                remaining_s=round(BUDGET - (time.time() - T_START), 1),
            )
            break
        _PROBE_BACKOFF_S += sleep_s
        _flight_heartbeat(
            "probe_backoff", attempt=attempt,
            backoff_s=round(sleep_s, 1),
            backoff_total_s=round(_PROBE_BACKOFF_S, 1),
        )
        print(
            f"backend probe failed ({detail}); retrying in {sleep_s:.1f}s",
            file=sys.stderr,
        )
        time.sleep(sleep_s)
    return False, detail, retries, time.time() - t_probe

def static_wave_cost(res: int, spp: int, timeout_s: float = 150.0) -> dict:
    """Static per-wave roofline of the production-shaped pool drain
    (tpu_pbrt/analysis/cost.py --bench-wave), computed in a CPU
    SUBPROCESS: a pure jaxpr trace that needs NO accelerator (ISSUE 3),
    so these fields are there whether or not the backend was reachable.
    Returns {} on failure, after saying why on stderr (the judged
    metrics must never depend on this)."""
    if os.environ.get("BENCH_SKIP_STATIC"):
        return {}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_SKIP_STATIC", None)
    try:
        r = subprocess.run(
            [sys.executable, "-m", "tpu_pbrt.analysis.cost",
             "--bench-wave", "--res", str(res), "--spp", str(spp)],
            capture_output=True, text=True, timeout=timeout_s, env=env,
        )
        if r.returncode == 0 and r.stdout.strip():
            d = json.loads(r.stdout.strip().splitlines()[-1])
            return {
                k: d[k]
                for k in ("static_flops_per_wave", "static_bytes_per_wave",
                          "static_intensity",
                          # hbmcheck's per-job serve footprint + HBM
                          # budget headroom fraction (ISSUE 18) — absent
                          # from pre-PR-18 subprocess output, tolerated
                          "static_hbm_per_job", "hbm_headroom")
                if k in d
            }
        print(
            f"static wave cost subprocess rc={r.returncode}: "
            f"{(r.stderr or '').strip().splitlines()[-1:] or ['?']}",
            file=sys.stderr,
        )
    except Exception as e:  # noqa: BLE001 — advisory fields only
        print(f"static wave cost failed: {e}", file=sys.stderr)
    return {}


#: last completed throughput measurement, reported by the SIGTERM/exception
#: fallback so a mid-phase kill still lands the number we already have
_last_line = None


def remaining():
    return BUDGET - (time.time() - T_START)


def compute_mse(mse_res: int, mse_spp: int, ref_spp: int):
    """Accelerator render vs cached CPU reference -> per-pixel MSE, or None
    if the reference cache is missing (generate with tools/make_reference.py)
    or the budgeted render did not complete. The render budget is computed
    AFTER the scene build/compile so that unbudgeted phase can't push the
    total spend past BENCH_BUDGET_S."""
    import numpy as np

    from tools.make_reference import reference_path

    path = reference_path(mse_res, ref_spp)
    if not os.path.exists(path):
        return None
    with np.load(path) as z:
        ref = np.asarray(z["image"], np.float32)

    from tpu_pbrt.scenes import compile_api, make_killeroo_like

    api = make_killeroo_like(res=mse_res, spp=mse_spp)
    scene, integ = compile_api(api)
    result = integ.render(scene, max_seconds=max(remaining() - 10.0, 5.0))
    if result.completed_fraction < 1.0:
        print(
            f"mse render incomplete ({result.completed_fraction:.0%}) — skipping",
            file=sys.stderr,
        )
        return None
    img = np.asarray(result.image, np.float32)
    return float(np.mean((img - ref) ** 2))


def main() -> int:
    """Runs the legs, prints the JSON line, returns the exit code."""
    # --trace out.json exports the span timeline; unknown args are left
    # for the driver (bench is also run bare by scripts that predate it)
    import argparse

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--trace", default="")
    args, _ = ap.parse_known_args()

    # judged work shape (BASELINE.json: killeroo/crown @ 256spp)
    spp = int(os.environ.get("BENCH_SPP", "256"))
    res = int(os.environ.get("BENCH_RES", "512"))

    # classify an unreachable backend BEFORE touching jax in-process
    # (VERDICT r4 weak #1: a capture recorded 0.0 Mray/s because the
    # backend was down — an infra condition, not a perf one).
    # NOTHING on this path may import tpu_pbrt/jax: if the accelerator
    # runtime is what's hanging, an in-process import would stall the
    # capture before the bounded probe ever runs. Heartbeats use the
    # import-free writer; the static fields come from a subprocess.
    if not os.environ.get("BENCH_SKIP_PROBE"):
        ok, detail, retries, wait_s = probe_backend()
        if not ok:
            line = {
                "metric": "killeroo_like_path_mray_per_sec",
                "value": 0.0, "unit": "Mray/s", "vs_baseline": 0.0,
                "infra_outage": True,
                "error": f"accelerator backend unreachable ({detail}); "
                         "perf not measurable this capture",
                # the probe's own accounting + where the flight recorder
                # last heartbeat
                "probe_retries": retries,
                "probe_wait_seconds": round(wait_s, 1),
                "probe_backoff_seconds": round(_PROBE_BACKOFF_S, 1),
                "flight_phase": _last_phase,
                "flight_path": _FLIGHT_PATH,
            }
            # the static half of the perf signal survives the outage:
            # per-wave roofline from a CPU-side jaxpr trace (ISSUE 3)
            if remaining() > 60:
                line.update(static_wave_cost(
                    res, spp, timeout_s=max(min(remaining() - 20, 150), 30)
                ))
            # the telemetry block exists even through an outage so rows
            # stay schema-comparable; the live half is null by
            # definition (inline literal — obs.rooflive would import
            # tpu_pbrt, see above)
            line["telemetry"] = {
                "counters": None, "wave_spread": None,
                "phase_seconds": None,
                "host_overlap_fraction": None,
                "live_bytes_per_sec": None, "live_flops_per_sec": None,
                "hbm_peak_bytes_per_sec": None,
                "live_vs_static_ratio": None,
            }
            _flight_heartbeat("report", infra_outage=True, retries=retries)
            print(json.dumps(line))
            return 2
        print(f"backend: {detail}", file=sys.stderr)

    # backend reachable: from here on tpu_pbrt (and jax) are safe to
    # import — hand the flight file over to the real recorder and arm
    # the span recorder
    from tpu_pbrt.config import place_compile_cache
    from tpu_pbrt.obs.compiles import COMPILES
    from tpu_pbrt.obs.flight import FLIGHT
    from tpu_pbrt.obs.trace import TRACE

    place_compile_cache()
    tracker = COMPILES.install()
    FLIGHT.configure(_FLIGHT_PATH, t0=T_START)
    if args.trace:
        TRACE.configure(args.trace)

    from tpu_pbrt.scenes import compile_api, make_killeroo_like

    FLIGHT.heartbeat("scene_compile", res=res, spp=spp)
    # scene_compile_seconds: parse + BVH build + device upload, measured
    # SEPARATELY from compile_seconds (XLA jit) — the two costs a warm
    # render-service residency hit (ISSUE 6) eliminates are exactly
    # these, so the trajectory needs them apart to credit the win
    _t_scene = time.time()
    with TRACE.span("bench/scene_compile"):
        api = make_killeroo_like(res=res, spp=spp)
        scene, integ = compile_api(api)
    scene_compile_seconds = time.time() - _t_scene

    # Warmup: a tightly budgeted pass populates the jit cache (identical
    # shapes). Its result doubles as the fallback measurement if compile
    # ate the budget — a compile-tainted number still beats no number.
    FLIGHT.heartbeat("warmup")
    with TRACE.span("bench/warmup"):
        result = integ.render(scene, max_seconds=5)
    programs_after_warmup = tracker.programs
    if remaining() > 60:
        # steady-state throughput stabilizes well before completion; box
        # the main leg so the MSE and crown legs fit the total budget
        FLIGHT.heartbeat("measure")
        with TRACE.span("bench/measure"):
            result = integ.render(
                scene,
                max_seconds=min(
                    remaining() - 30.0, max(60.0, remaining() * 0.22)
                ),
            )

    # measured rays per camera ray from the run just completed (the class
    # default attribute is a lower bound; the real factor includes bounces
    # and shadow segments)
    cam_rays = res * res * spp * max(result.completed_fraction, 1e-6)
    rays_ratio = max(result.rays_traced / max(cam_rays, 1.0), 1.0)

    north_star = 100.0  # Mray/s on v5e-8 (BASELINE.json north_star)
    # sanity channel: a black render means the tracer is broken even if
    # the ray counter ticked — Mray/s over a broken image is not a result
    import numpy as np

    img_mean = float(np.mean(np.asarray(result.image, np.float32)))
    global _last_line
    _last_line = {
        "metric": "killeroo_like_path_mray_per_sec",
        "value": round(result.mray_per_sec, 3),
        "unit": "Mray/s",
        "vs_baseline": round(result.mray_per_sec / north_star, 4),
        "completed_fraction": round(result.completed_fraction, 4),
        "rays_traced": result.rays_traced,
        "seconds": round(result.seconds, 2),
        "image_mean": round(img_mean, 6),
    }
    # persistent-wavefront occupancy (ISSUE 1): live lanes per trace wave
    # under regeneration — the trajectory metric next to Mray/s
    occ = result.stats.get("mean_wave_occupancy")
    if occ is not None:
        _last_line["mean_wave_occupancy"] = round(float(occ), 4)
        _last_line["trace_waves"] = int(result.stats.get("n_waves", 0))
        _last_line["pool"] = int(result.stats.get("pool", 0))
    # compile accounting (jaxlint audit's recompile guard, measured in
    # the judged run): programs built or loaded during the steady-state
    # leg must be 0 — the warmup pass owns every legitimate trace for
    # these shapes. compile_cache_warm says whether the persistent cache
    # (config.place_compile_cache) supplied every one of them.
    _last_line["jit_recompiles"] = tracker.programs - programs_after_warmup
    _last_line["compile_seconds"] = round(tracker.seconds, 2)
    _last_line["scene_compile_seconds"] = round(scene_compile_seconds, 2)
    if tracker.cache_hits and not tracker.cache_misses:
        _last_line["compile_cache_warm"] = True
    if not (img_mean > 1e-6):
        _last_line["error"] = "image is black — tracer broken"

    # crown-class row (VERDICT r4 #5): >=1M-tri glass+metal-GGX+HDR-env
    # scene, reported as crown_* fields of the same JSON line (the
    # driver parses exactly one line). Runs BEFORE the MSE leg but
    # reserves its predicted cost so the judged accuracy number is
    # never starved.
    crown = None
    failed_legs = []  # legs that raised: the line keeps what was measured
    mse_res = int(os.environ.get("MSE_RES", "128"))
    mse_spp = int(os.environ.get("MSE_SPP", "256"))
    est_rays = mse_res * mse_res * mse_spp * rays_ratio
    mse_reserve = (
        0.0 if os.environ.get("BENCH_SKIP_MSE")
        # + ~95 s: the 128^2 MSE scene is a different shape and pays its
        # own jit compile, which est_rays/throughput cannot see
        else est_rays / max(result.mray_per_sec, 1e-6) / 1e6 + 95.0
    )
    if not os.environ.get("BENCH_SKIP_CROWN") and remaining() - mse_reserve > 90:
        try:
            from tpu_pbrt.scenes import make_crown_like

            FLIGHT.heartbeat("crown")
            with TRACE.span("bench/crown"):
                capi = make_crown_like(
                    res=int(os.environ.get("CROWN_RES", "512")),
                    spp=int(os.environ.get("CROWN_SPP", "256")),
                )
                cscene, cinteg = compile_api(capi)
                cinteg.render(cscene, max_seconds=5)  # warmup (jit compile)
                # the 1M-tri compile above is unbudgeted: re-check that
                # the judged MSE leg still fits before spending more here
                budget = remaining() - mse_reserve - 15.0
                if budget < 10.0:
                    raise RuntimeError("crown skipped post-compile: budget")
                cres = cinteg.render(cscene, max_seconds=budget)
            import numpy as _np

            cmean = float(_np.mean(_np.asarray(cres.image, _np.float32)))
            crown = {
                "crown_mray_per_sec": round(cres.mray_per_sec, 3),
                "crown_completed_fraction": round(cres.completed_fraction, 4),
                "crown_rays_traced": cres.rays_traced,
                "crown_image_mean": round(cmean, 6),
            }
            _last_line.update(crown)
        except Exception as e:  # noqa: BLE001 — reported; fails the run below
            crown = {"crown_error": f"{type(e).__name__}: {e}"}
            failed_legs.append("crown")
    elif not os.environ.get("BENCH_SKIP_CROWN"):
        print(f"skipping crown row: {remaining():.0f}s left", file=sys.stderr)

    mse = None
    if not os.environ.get("BENCH_SKIP_MSE"):
        try:
            mse_res = int(os.environ.get("MSE_RES", "128"))
            mse_spp = int(os.environ.get("MSE_SPP", "256"))
            # predicted cost of the MSE render from measured throughput
            est_rays = mse_res * mse_res * mse_spp * rays_ratio
            est_s = est_rays / max(result.mray_per_sec, 1e-6) / 1e6 + 30.0
            budget = remaining() - 20.0
            if est_s < budget:
                FLIGHT.heartbeat("mse")
                with TRACE.span("bench/mse"):
                    mse = compute_mse(
                        mse_res, mse_spp,
                        int(os.environ.get("REF_SPP", "256")),
                    )
            else:
                print(
                    f"skipping MSE: est {est_s:.0f}s > budget {budget:.0f}s",
                    file=sys.stderr,
                )
        except Exception as e:  # noqa: BLE001 — reported; fails the run below
            print(f"mse computation failed: {e}", file=sys.stderr)
            failed_legs.append("mse")

    # static per-wave roofline next to the measured occupancy (ISSUE 3):
    # the same fields the outage path emits, so BENCH rows stay
    # comparable across infra-up and infra-down captures. Runs LAST —
    # it is advisory and must never starve the judged crown/MSE legs.
    if remaining() > 45:
        with TRACE.span("bench/static_cost"):
            _last_line.update(static_wave_cost(
                res, spp, timeout_s=max(min(remaining() - 15, 150), 30)
            ))

    # telemetry block (ISSUE 4): device counters + per-device wave-count
    # spread from the measured leg, and the live-vs-static roofline
    # ratio closing the loop on the static fields above (null on CPU or
    # when the static trace failed — the block is always present so
    # BENCH rows stay schema-comparable)
    import jax as _jax

    from tpu_pbrt.obs.metrics import host_overlap_fraction, phase_summary
    from tpu_pbrt.obs.rooflive import live_vs_static

    tstats = result.stats.get("telemetry") or {}
    devs = _jax.devices()
    _last_line["telemetry"] = {
        "counters": tstats.get("counters"),
        "wave_spread": tstats.get("wave_spread"),
        # per-phase wall-time histogram summary (ISSUE 10): dispatch vs
        # device-wait vs deposit-develop vs checkpoint across every leg
        # this process ran (null under TPU_PBRT_METRICS=0; rows stay
        # schema-comparable)
        "phase_seconds": phase_summary(),
        # device_wait / measured wall over the MEASURED leg (ISSUE 13):
        # 1.0 = the host tax (deposit/develop/checkpoint bookkeeping)
        # fully hidden under in-flight dispatch — the pipelined-drain
        # acceptance number, strictly better at TPU_PBRT_PIPELINE=2
        # than the depth-1 synchronous baseline
        "host_overlap_fraction": host_overlap_fraction(
            result.stats.get("phase_seconds"), result.seconds
        ),
        **live_vs_static(
            waves=result.stats.get("n_waves"),
            seconds=result.seconds,
            static_bytes_per_wave=_last_line.get("static_bytes_per_wave"),
            static_flops_per_wave=_last_line.get("static_flops_per_wave"),
            device_kind=getattr(devs[0], "device_kind", devs[0].platform),
            n_devices=len(devs),
        ),
    }
    if tstats.get("counters"):
        FLIGHT.counters(tstats["counters"], phase="measure_counters")

    line = dict(_last_line)
    if mse is not None:
        line["mse_vs_cpu_ref"] = mse
        line["mse_target"] = 1e-4
    if crown:
        line.update(crown)
    FLIGHT.heartbeat("report", mray_per_sec=line.get("value"))
    TRACE.maybe_export()
    from tpu_pbrt.obs.metrics import METRICS

    METRICS.maybe_export()  # TPU_PBRT_METRICS_PATH snapshot, if armed
    if failed_legs:
        line["failed_legs"] = failed_legs
    print(json.dumps(line))
    return 1 if failed_legs or "error" in line else 0


def _on_term(signum, frame):
    raise RuntimeError(f"signal {signum}")


if __name__ == "__main__":
    import signal

    # `timeout` sends SIGTERM before SIGKILL: convert it into an exception
    # so the fallback line below still prints under a driver timeout
    signal.signal(signal.SIGTERM, _on_term)
    try:
        sys.exit(main())
    except Exception as e:  # noqa: BLE001 — print a parseable line, then fail
        line = dict(_last_line) if _last_line else {
            "metric": "killeroo_like_path_mray_per_sec",
            "value": 0.0,
            "unit": "Mray/s",
            "vs_baseline": 0.0,
        }
        line["error"] = f"{type(e).__name__}: {e}"
        # the flight recorder's last phase turns "signal 15" into "died
        # mid-<phase> after N s" for the post-mortem. Only touch the
        # real recorder if tpu_pbrt ALREADY imported — a fatal during a
        # hung-runtime capture must not start the import that hangs.
        try:
            mod = sys.modules.get("tpu_pbrt.obs.flight")
            if mod is not None and mod.FLIGHT.last_phase is not None:
                line["flight_phase"] = mod.FLIGHT.last_phase
            else:
                line["flight_phase"] = _last_phase
            _flight_heartbeat("fatal", error=line["error"])
            tmod = sys.modules.get("tpu_pbrt.obs.trace")
            if tmod is not None:
                tmod.TRACE.maybe_export()
        except Exception:  # noqa: BLE001 — telemetry must not mask the error
            pass
        print(json.dumps(line))
        sys.exit(1)
