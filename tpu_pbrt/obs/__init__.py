"""tpu-trace: runtime telemetry for the renderer (ISSUE 4).

One piece per module:

- `counters`  — a device-side per-wave counter block (pure jnp state)
  threaded through the persistent-wavefront drain loop and fetched
  exactly once at the drain boundary, so the bounce loop stays
  transfer-guard-clean and retrace-free;
- `trace`     — a host-side span recorder with Chrome-trace/Perfetto
  JSON export (`--trace` on main.py / bench.py);
- `flight`    — an append-only JSONL flight recorder (phase heartbeats +
  counter snapshots + backend probe state) so an infra-outage capture
  carries a diagnosis instead of a bare error string;
- `phases`    — the one table of device-program phase names every
  `jax.named_scope` of the program takes its name from;
- `devtrace`  — device time by phase from a `jax.profiler` trace alone
  (`python -m tpu_pbrt.obs phases FILE.xplane.pb`; `tpu_pbrt.main
  --profile DIR` prints it at exit);
- `rooflive`  — live-vs-static roofline cross-check of measured wave
  rates against the committed static budgets (analysis/budgets.json);
- `compiles`  — process-wide account of what jax traced, lowered, built
  and loaded: seconds by stage and by program, persistent-cache hits and
  misses (the compile-refusal test in ChunkPlan.dispatch, the stage
  seconds the set-up path's spans carry, what every entry point and the
  benchmark's set-up metrics report);
- `metrics`   — process-wide host-side metrics registry (ISSUE 10):
  counters/gauges/fixed-bucket histograms with bucket-derived
  percentiles, Prometheus text exposition, render-phase attribution
  and the serve SLO load-shedding inputs (`TPU_PBRT_METRICS=0` kills).

All of it is default-on behind `TPU_PBRT_TELEMETRY` (=0 kills it and
compiles the exact pre-telemetry device program); `python -m
tpu_pbrt.obs` validates exported trace/flight files (the CI smoke
stage's gate) and `python -m tpu_pbrt.obs phases` reduces a profile.

Submodules are resolved LAZILY: `counters` imports jax at module level,
and an eager import here would drag jax into every `tpu_pbrt.obs.*`
consumer — including bench.py's outage path, which must stay bounded
when the accelerator runtime itself is what's hanging.
"""

import importlib

_SUBMODULES = (
    "compiles", "counters", "devtrace", "flight", "metrics", "phases",
    "rooflive", "trace",
)


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f"tpu_pbrt.obs.{name}")
    raise AttributeError(f"module 'tpu_pbrt.obs' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_SUBMODULES))
