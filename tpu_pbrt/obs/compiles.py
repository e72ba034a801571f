"""Process-wide account of what jax traced, lowered, compiled and loaded.

One set of listeners on the supported `jax.monitoring` event stream, shared
by everything that needs to know whether a call compiled and what that cost.
A program goes through three stages, each timed by jax under its own event
(`jax/_src/dispatch.py::LogElapsedTimeContextManager`, which also names the
program):

    trace    Python runs the function under jit -> a jaxpr      `trace_seconds`
    lower    jaxpr -> the StableHLO module (the cache's key)    `lower_seconds`
    backend  XLA builds it, or the persistent cache loads it    `seconds`

A warm persistent cache (config.place_compile_cache) saves the third only:
it is keyed by the LOWERED module, so every process traces and lowers every
program again. `retrieval_seconds` is the part of `seconds` spent reading
cache entries; `cache_misses` counts the programs XLA built and WROTE to the
cache (jax records the event at the write, so the tiny eager programs under
the cache's size and time thresholds never count).

A jitted function called while another is traced is traced inside it, and
jax times both: the totals and the rows keep the OUTERMOST trace of each
thread only, so nothing is counted twice. `traces` counts every one.

Who reads which field:

- `traces`: `ChunkPlan.dispatch` (integrators/common.py), around a
  dispatch: a runtime error out of a call that had to trace is a COMPILE
  refusal (deterministic, surfaced once with the compiler's message) where
  the same error out of a call that only executed is a device loss for the
  recovery ladder;
- `programs`: `WavefrontIntegrator.render` (`programs_after_first_chunk`:
  a steady state shows no growth at all) and the benchmark's
  `programs_in_window`; executables made ready, whether XLA built them or
  the cache supplied them (`cache_hits` / `cache_misses` tell the two apart);
- `seconds`: the benchmark's `program_ready_s`, `bench.py`;
- `stages_into(span)`: the spans of the set-up path
  (`render/prepare_chunks`, `render/capacity_audit`,
  `render/chunk_dispatch+compile`) carry what fell inside them in their
  `args`, so a trace says which step traced, which the cache served and
  which XLA built;
- `snapshot()`: `tpu_pbrt.main` with every render, the serve daemon's
  `stats` verb and `chip_smoke.py` through `process_report()`; the benchmark
  around its window (`notes.compiles_before` / `compiles_after`), where
  `program_trace_s`, `program_lower_s` and `programs_built_in_setup` read
  `trace_seconds`, `lower_seconds` and `cache_misses`;
- `by_program()`: the rows by program, largest first; `snapshot()` carries
  the first few, so every benchmark run's notes say which program the
  seconds went to.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, List, Optional

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

#: rows `by_program` keeps (a daemon must not grow); names past it share OTHER
MAX_PROGRAM_ROWS = 256
OTHER = "other"
#: rows `snapshot()` carries
SNAPSHOT_ROWS = 6
#: the counters a span of the set-up path carries the growth of
STAGE_FIELDS = (
    "trace_seconds", "lower_seconds", "seconds", "retrieval_seconds",
    "cache_hits", "cache_misses",
)


def _program(fun_name) -> str:
    """jax names a program by its function at the trace (`chunk_fn`) and by
    its module at the later stages (`jit(chunk_fn)`): one row for both."""
    name = str(fun_name or OTHER)
    for prefix in ("jit(", "pmap("):
        if name.startswith(prefix) and name.endswith(")"):
            return name[len(prefix):-1]
    return name


class CompileTracker:
    def __init__(self) -> None:
        self.traces = 0
        self.programs = 0
        self.seconds = 0.0
        self.trace_seconds = 0.0
        self.lower_seconds = 0.0
        self.retrieval_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        #: program -> [outermost traces, programs, trace s, lower s, backend s]
        self._rows: Dict[str, list] = {}
        self._open = threading.local()  # .depth: traces open on this thread
        self._installed = False

    def _row(self, fun_name) -> list:
        name = _program(fun_name)
        row = self._rows.get(name)
        if row is None:
            if len(self._rows) >= MAX_PROGRAM_ROWS:
                name = OTHER
            row = self._rows.setdefault(name, [0, 0, 0.0, 0.0, 0.0])
        return row

    def install(self) -> "CompileTracker":
        """Start listening (idempotent). Counts start at the first call:
        entry points call this before their first jit."""
        if self._installed:
            return self
        import jax.monitoring

        jax.monitoring.register_scalar_listener(self._on_scalar)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self._installed = True
        return self

    # the three listeners, with jax.monitoring's signatures

    def _on_scalar(self, event, value, fun_name=None, **kw) -> None:
        # jax records the START of each timed stage as a scalar
        if event == _TRACE_EVENT:
            self._open.depth = getattr(self._open, "depth", 0) + 1

    def _on_duration(self, event, duration, fun_name=None, **kw) -> None:
        if event == _TRACE_EVENT:
            self.traces += 1
            depth = self._open.depth = max(getattr(self._open, "depth", 1) - 1, 0)
            if depth == 0:
                self.trace_seconds += duration
                row = self._row(fun_name)
                row[0] += 1
                row[2] += duration
        elif event == _LOWER_EVENT:
            self.lower_seconds += duration
            self._row(fun_name)[3] += duration
        elif event == _BACKEND_EVENT:
            self.programs += 1
            self.seconds += duration
            row = self._row(fun_name)
            row[1] += 1
            row[4] += duration
        elif event == _RETRIEVAL_EVENT:
            self.retrieval_seconds += duration

    def _on_event(self, event, **kw) -> None:
        if event == _HIT_EVENT:
            self.cache_hits += 1
        elif event == _MISS_EVENT:
            self.cache_misses += 1

    @contextmanager
    def stages_into(self, span):
        """Put into `span.args` (a `TRACE.span`'s Span) how far each of the
        STAGE_FIELDS grew inside the with-body, under `compile_<field>`:
        all zero says the body built and loaded nothing."""
        before = [getattr(self, f) for f in STAGE_FIELDS]
        try:
            yield
        finally:
            for f, was in zip(STAGE_FIELDS, before):
                span.args[f"compile_{f}"] = round(getattr(self, f) - was, 6)

    def by_program(self, top: Optional[int] = None) -> List[Dict[str, object]]:
        """One row a program (at most MAX_PROGRAM_ROWS and OTHER), the one
        with the most seconds first; `top` cuts the list there."""
        rows = sorted(self._rows.items(), key=lambda kv: -sum(kv[1][2:]))
        return [
            {
                "program": name, "traces": r[0], "programs": r[1],
                "trace_seconds": round(r[2], 3), "lower_seconds": round(r[3], 3),
                "backend_seconds": round(r[4], 3),
            }
            for name, r in rows[:top]
        ]

    def snapshot(self) -> Dict[str, object]:
        return {
            "programs": self.programs,
            "compile_seconds": round(self.seconds, 3),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "traces": self.traces,
            "trace_seconds": round(self.trace_seconds, 3),
            "lower_seconds": round(self.lower_seconds, 3),
            "retrieval_seconds": round(self.retrieval_seconds, 3),
            "by_program": self.by_program(SNAPSHOT_ROWS),
        }


#: the process-wide tracker (one set of listeners for the process)
COMPILES = CompileTracker()


def process_report() -> Dict[str, object]:
    """What this process runs on and what it has compiled: the device as
    jax reports it, the jax version, the BVH builder in use, where the
    persistent compile cache lives, and the tracker's snapshot. Printed by
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
