"""Driver `frames`: one client renders whole frames, back to back.

The entry the window drives is the one `tpu_pbrt.main` reaches through
`render_file`: a `.pbrt` file -> `pbrt_init` / parser -> `compile_scene` ->
`make_integrator` -> `WavefrontIntegrator.render(scene)`. The scene is
compiled once in set-up (`compile_file`), `render` is called once per frame.

A traffic file that names this driver may set:

    mesh          true: Options.mesh_shape = (chips,), as `--mesh` sets it
    trace_seconds stop the profiler that many seconds into the traced frame
                  (the frame itself runs to its end): for cells whose whole
                  frame is too many device events to bring back and reduce

Warm-up is `render(max_seconds=tiny)`, which stops at a dispatch boundary
after a dispatch or two and builds or loads every program a frame uses
(`programs_in_window` reads 0 after it, PERF.md). A `--trace 1` run profiles
the window's first frame.

The driver fills `ctx` (a dict) with what the metric readers read:

    setup_s, window_s, frames (one dict per frame started: seconds, ok,
    rays_traced, completed_fraction, stats), traced_index, scene_compile_s,
    compiles_before / compiles_after (COMPILES snapshots around the window),
    compile_seconds_setup, trace (the reduced profile, traced runs only)

and keeps the last frame's developed image and filter weights for the
comparison (`film(ctx)`), until `release(ctx)` frees the program's state.
"""

from __future__ import annotations

import gc
import glob
import os
import shutil
import threading
import time


def _device_gate(ctx) -> None:
    import jax

    devs = jax.devices()
    ctx["device"] = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    chips = int(ctx["cell"]["chips"])
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s), jax sees {len(devs)}")
    if devs[0].platform == "cpu" and not ctx["rehearse"]:
        raise SystemExit("jax found no accelerator (platform=cpu)")
    ctx["devices_used"] = devs[:chips] if ctx["traffic"].get("mesh") else devs[:1]


def setup(ctx) -> None:
    """Everything before the window; ends with every program of the cell
    built or loaded. `ctx["t_start"]` is the process's start."""
    from tpu_pbrt.config import place_compile_cache
    from tpu_pbrt.obs.compiles import COMPILES

    place_compile_cache()
    COMPILES.install()
    _device_gate(ctx)

    from tpu_pbrt.scene.api import Options, compile_file

    t = time.monotonic()
    desc = ctx["scene_writer"].build(ctx["config"], ctx["seed"])
    ctx["desc"] = desc
    path = ctx["write_scene"](desc, ctx["work_dir"], "scene")
    mesh = (int(ctx["cell"]["chips"]),) if ctx["traffic"].get("mesh") else None
    scene, integ = compile_file(path, Options(quiet=True, mesh_shape=mesh))
    ctx["scene_compile_s"] = time.monotonic() - t
    ctx["_scene"], ctx["_integ"] = scene, integ

    t = time.monotonic()
    integ.render(scene, max_seconds=1e-3)
    ctx["warmup_s"] = time.monotonic() - t
    ctx["compile_seconds_setup"] = COMPILES.seconds
    ctx["setup_s"] = time.monotonic() - ctx["t_start"]


class _Stopper:
    """Stops the profiler once: from a timer `seconds` after `arm()` where
    seconds > 0, else (or if the frame ended first) from `now()`."""

    def __init__(self, seconds: float):
        self.seconds, self.lock, self.done, self.timer = seconds, threading.Lock(), False, None

    def arm(self) -> None:
        if self.seconds > 0:
            self.timer = threading.Timer(self.seconds, self.now)
            self.timer.daemon = True
            self.timer.start()

    def now(self) -> None:
        import jax

        with self.lock:
            if not self.done:
                self.done = True
                jax.profiler.stop_trace()
        if self.timer is not None and threading.current_thread() is not self.timer:
            self.timer.join()


def _one_frame(ctx, frames) -> None:
    scene, integ = ctx["_scene"], ctx["_integ"]
    t = time.monotonic()
    rec = {"ok": False}
    frames.append(rec)
    try:
        r = integ.render(scene)
    except Exception as e:  # a frame that raised is a failed operation
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
        rec["seconds"] = time.monotonic() - t
        return
    rec.update(
        seconds=time.monotonic() - t,
        render_seconds=r.seconds,
        rays_traced=int(r.rays_traced),
        completed_fraction=float(r.completed_fraction),
        stats=r.stats,
        ok=r.completed_fraction == 1.0 and not r.stats.get("truncated_chunks"),
    )
    ctx["_last"] = r


def window(ctx) -> None:
    """Whole frames back to back until `seconds` have passed; the frame in
    flight then finishes and the window closes there."""
    import jax

    from tpu_pbrt.obs.compiles import COMPILES

    seconds, trace = float(ctx["seconds"]), bool(ctx["trace"])
    traced = ctx["traced_index"] = 0
    trace_dir = os.path.join(ctx["work_dir"], "trace")
    frames = ctx["frames"] = []
    ctx["compiles_before"] = COMPILES.snapshot()
    t0 = time.monotonic()
    while True:
        k = len(frames)
        if trace and k == traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            stop = _Stopper(float(ctx["traffic"].get("trace_seconds", 0.0)))
            try:
                with jax.profiler.TraceAnnotation("bench/between"):
                    pass
                with jax.profiler.TraceAnnotation("bench/frame_begin"):
                    stop.arm()
                with jax.profiler.TraceAnnotation("bench/frame"):
                    _one_frame(ctx, frames)
                with jax.profiler.TraceAnnotation("bench/between"):
                    pass
            finally:
                stop.now()
        else:
            _one_frame(ctx, frames)
        if not frames[-1]["ok"] or time.monotonic() - t0 >= seconds:
            break
    ctx["window_s"] = time.monotonic() - t0
    ctx["compiles_after"] = COMPILES.snapshot()
    ctx["attempted"] = len(frames)
    ctx["frame_seconds"] = [f["seconds"] for f in frames]
    ctx["failed"] = sum(1 for f in frames if not f["ok"])
    stats = [d.memory_stats() or {} for d in ctx["devices_used"]]
    ctx["memory_peak_bytes"] = max((int(s.get("peak_bytes_in_use", 0)) for s in stats), default=0)
    if trace and len(frames) > traced:
        found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if found:
            ctx["xplane_path"] = found[-1]


def film(ctx):
    """(image (H,W,3), filter weights (H,W)) of the window's last frame."""
    import jax
    import numpy as np

    r = ctx.get("_last")
    if r is None:
        return None
    return np.asarray(r.image), np.asarray(jax.device_get(r.film_state.weight))


def release(ctx) -> None:
    """Free the program's state on the device before the reference runs."""
    import jax

    for k in ("_last", "_scene", "_integ"):
        ctx.pop(k, None)
    gc.collect()
    jax.clear_caches()
    gc.collect()
