"""Process-wide count of what jax traced, compiled and loaded.

One listener on the supported `jax.monitoring` event stream, shared by
everything that needs to know whether a call compiled:

- `ChunkPlan.dispatch` (integrators/common.py) reads `traces` around a
  dispatch: a runtime error out of a call that had to trace is a
  COMPILE refusal — deterministic, surfaced once with the compiler's
  message — where the same error out of a call that only executed is a
  device loss for the recovery ladder;
- `tpu_pbrt.main`, the serve daemon's `stats` verb, `bench.py` and
  `chip_smoke.py` report `snapshot()` so a run says how many programs it
  built, how long that took, and whether the persistent cache
  (config.place_compile_cache) was warm.

`programs` counts executables made ready, whether XLA built them or the
persistent cache supplied them (jax times both under the same event);
`cache_hits`/`cache_misses` tell the two apart. A steady state shows no
growth in `programs` at all.
"""

from __future__ import annotations

from typing import Dict

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileTracker:
    def __init__(self) -> None:
        self.traces = 0
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self._installed = False

    def install(self) -> "CompileTracker":
        """Start listening (idempotent). Counts start at the first call:
        entry points call this before their first jit."""
        if self._installed:
            return self
        import jax.monitoring

        def on_duration(event, duration, **kw):
            if event == _TRACE_EVENT:
                self.traces += 1
            elif event == _BACKEND_EVENT:
                self.programs += 1
                self.seconds += duration

        def on_event(event, **kw):
            if event == _HIT_EVENT:
                self.cache_hits += 1
            elif event == _MISS_EVENT:
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        self._installed = True
        return self

    def snapshot(self) -> Dict[str, float]:
        return {
            "programs": self.programs,
            "compile_seconds": round(self.seconds, 3),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
        }


#: the process-wide tracker (listeners cannot be unregistered, so one)
COMPILES = CompileTracker()


def process_report() -> Dict[str, object]:
    """What this process runs on and what it has compiled: the device as
    jax reports it, the jax version, the BVH builder in use, where the
    persistent compile cache lives, and the tracker's counts. Printed by
    `tpu_pbrt.main` with every render and answered by the serve daemon's
    `stats` verb, so no run hides which device did the work."""
    import jax

    from tpu_pbrt.accel.native import builder_name

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "devices": len(devs),
        "jax": jax.__version__,
        "bvh_builder": builder_name(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        **COMPILES.snapshot(),
    }
