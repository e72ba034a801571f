"""Centralized runtime configuration — the ONLY sanctioned os.environ
reader inside tpu_pbrt/ (enforced by jaxlint rule JL-ENV).

Every TPU_PBRT_* knob the renderer honors is read ONCE, here, at import
time into the module-level `cfg` singleton. Hot modules import `cfg` and
read plain attributes — no scattered `os.environ.get` calls inside
jit-reachable code, no per-call string parsing, and one place to see the
whole knob surface.

Tests that need to flip a knob mid-process set the env var and call
`reload()` (see tests/conftest.py's `tpu_pbrt_env` helper); production
code must never call reload() — the snapshot taken at import is the
contract.
"""

from __future__ import annotations

import os
from typing import Optional


_FALSY = frozenset({"0", "false", "no", "off"})
_TRUTHY = frozenset({"1", "true", "yes", "on"})


def _flag(name: str, default: bool) -> bool:
    """Explicit falsy/truthy spellings only; unset, empty, or anything
    unrecognized keeps the default. `export KNOB=` or `KNOB=false` in a
    wrapper script must never count as enabled — TPU_PBRT_ALLOW_DROPS
    silently flipping on would downgrade the capacity-overflow error to
    a warning (silent false misses)."""
    v = os.environ.get(name)
    if v is None:
        return default
    v = v.strip().lower()
    if v in _FALSY:
        return False
    if v in _TRUTHY:
        return True
    return default


def _int(name: str, default: Optional[int]) -> Optional[int]:
    v = os.environ.get(name)
    return default if v in (None, "") else int(v)


def _float(name: str, default: Optional[float]) -> Optional[float]:
    v = os.environ.get(name)
    return default if v in (None, "") else float(v)


class Config:
    """Snapshot of every environment knob. Attributes only — no methods
    touch os.environ after _load()."""

    __slots__ = (
        "bvh",
        "leaf_tris",
        "onehot",
        "slab",
        "headroom",
        "native",
        "progress_frequency",
        "coordinator_address",
        "regen",
        "mipfilter",
        "chunk",
        "pool",
        "deposit_seg",
        "serve_chunk",
        "serve_resident_mb",
        "pipeline",
        "serve_prefetch",
        "audit_drops",
        "allow_drops",
        "telemetry",
        "metrics",
        "metrics_path",
        "trace_path",
        "flight_path",
        "flight_max_mb",
        "metrics_exemplars",
        "health_wedge_steps",
        "serve_slo_depth",
        "serve_slo_wait_s",
        "faults",
        "nonfinite",
        "retry_max",
        "retry_backoff",
        "retry_backoff_cap",
        "retry_deadline",
    )

    def _load(self) -> "Config":
        #: acceleration structure: stream (default) | packet | wide | binary
        self.bvh: str = os.environ.get("TPU_PBRT_BVH", "stream")
        #: triangles per stream-path treelet leaf (None -> STREAM_LEAF_TRIS)
        self.leaf_tris: Optional[int] = _int("TPU_PBRT_LEAF_TRIS", None)
        #: one-hot MXU matmul for small-table gathers in EXPAND
        self.onehot: bool = _flag("TPU_PBRT_ONEHOT", True)
        #: stream worklist slab cap (pairs per EXPAND step)
        self.slab: int = _int("TPU_PBRT_SLAB", 1 << 17)
        #: worklist headroom scale (the overflow regression test shrinks it)
        self.headroom: float = _float("TPU_PBRT_HEADROOM", 1.0)
        #: native C++ scene-compile helpers (0 forces the numpy builders)
        self.native: bool = _flag("TPU_PBRT_NATIVE", True)
        #: progress-bar min update interval in seconds (pbrt's knob name)
        self.progress_frequency: Optional[float] = _float(
            "PBRT_PROGRESS_FREQUENCY", None
        )
        #: multi-host coordinator snapshot; prefer coordinator_address()
        #: (call-time) — drivers commonly export the variable AFTER
        #: import, once cluster discovery has run
        self.coordinator_address: Optional[str] = os.environ.get(
            "JAX_COORDINATOR_ADDRESS"
        )
        #: persistent-wavefront in-place regeneration (0 -> fixed batch)
        self.regen: bool = _flag("TPU_PBRT_REGEN", True)
        #: trilinear mip selection from camera-ray differentials
        self.mipfilter: bool = _flag("TPU_PBRT_MIPFILTER", True)
        #: camera rays per dispatch (None -> platform default)
        self.chunk: Optional[int] = _int("TPU_PBRT_CHUNK", None)
        #: path-pool slots (0 -> per_dev/4 heuristic)
        self.pool: int = _int("TPU_PBRT_POOL", 0)
        #: segmented pool film deposit: width of the per-wave deposit
        #: window (terminated lanes are sorted to a contiguous prefix and
        #: only the window is scattered — the full-pool-width scatter was
        #: the ROADMAP "pool deposit path" carried item). 0 = auto
        #: (pool/4 once the pool is big enough to amortize the extra
        #: sort); >= pool or negative = full-width (the exact pre-segment
        #: program)
        self.deposit_seg: int = _int("TPU_PBRT_DEPOSIT_SEG", 0)
        #: in-flight dispatch window (ISSUE 13): how many chunk-slices
        #: the drain loops keep launched ahead of the host. JAX dispatch
        #: is async, so depth N lets every piece of host-side work
        #: (deposit bookkeeping, preview develop, checkpoint
        #: serialization, scheduling, metrics/flight recording) run
        #: UNDER the device compute of the slices still in flight; 1 is
        #: the strictly synchronous dispatch/block/host-work loop (the
        #: A/B baseline for host_overlap_fraction). Bit-identity is
        #: depth-independent by construction — the window only moves
        #: sync points, never the dispatched programs. The strict
        #: non-finite firewall modes force depth 1 (their per-chunk
        #: scrub-count sync cannot be pipelined away); see
        #: parallel/mesh.resolve_pipeline_depth
        self.pipeline: int = _int("TPU_PBRT_PIPELINE", 2)
        #: render-service dispatch lookahead: while the current job's
        #: slice is in flight, pre-activate the NEXT scheduled job
        #: (plan build + checkpoint film load host->HBM + residency LRU
        #: touch) so its first dispatch is not serialized behind its
        #: activation. Never preempts, never changes the schedule
        self.serve_prefetch: bool = _flag("TPU_PBRT_SERVE_PREFETCH", True)
        #: render-service slice width (camera rays per submit/step
        #: quantum — the preemption granularity; None = platform chunk)
        self.serve_chunk: Optional[int] = _int("TPU_PBRT_SERVE_CHUNK", None)
        #: render-service resident-scene HBM budget in MB (LRU eviction
        #: above it; None = unbounded). The default is a checked
        #: consequence of hbmcheck's serve HBM model (HC-CAP): the
        #: largest 1024-aligned budget that, together with the
        #: worst-case job load, fits the smallest platform's HBM with
        #: headroom — `python -m tpu_pbrt.analysis.hbmcheck
        #: --derive-hbm-caps` reproduces it
        self.serve_resident_mb: Optional[float] = _float(
            "TPU_PBRT_SERVE_RESIDENT_MB", 12288.0
        )
        #: pre-render stream-capacity audit (overflows fail loudly)
        self.audit_drops: bool = _flag("TPU_PBRT_AUDIT_DROPS", True)
        #: downgrade a detected capacity overflow to a warning
        self.allow_drops: bool = _flag("TPU_PBRT_ALLOW_DROPS", False)
        #: runtime telemetry (tpu_pbrt/obs): device-side wave counters in
        #: the pool drain, host-side trace spans and flight heartbeats.
        #: 0 is the kill switch — the drain compiles to the exact
        #: pre-telemetry program (the counter carry is a None pytree leaf)
        self.telemetry: bool = _flag("TPU_PBRT_TELEMETRY", True)
        #: host-side metrics registry (tpu_pbrt/obs/metrics.py):
        #: counters/gauges/histograms over the serve path and the render
        #: drain loop, Prometheus exposition, SLO load-shedding inputs.
        #: 0 is the kill switch — every record call is a no-op and render
        #: stats / serve responses are byte-identical to a build without
        #: the registry (host-side only; the compiled programs never see
        #: it either way)
        self.metrics: bool = _flag("TPU_PBRT_METRICS", True)
        #: Prometheus text snapshot file the registry exports to (also
        #: settable per-run via --metrics-path on main.py / serve)
        self.metrics_path: Optional[str] = os.environ.get(
            "TPU_PBRT_METRICS_PATH"
        ) or None
        #: Chrome-trace/Perfetto JSON output path for the span recorder
        #: (also settable per-run via --trace on main.py / bench.py)
        self.trace_path: Optional[str] = os.environ.get(
            "TPU_PBRT_TRACE_PATH"
        ) or None
        #: append-only JSONL flight-recorder path (phase heartbeats +
        #: counter snapshots; bench.py defaults this when unset)
        self.flight_path: Optional[str] = os.environ.get(
            "TPU_PBRT_FLIGHT_PATH"
        ) or None
        #: flight-recorder growth cap in MB: at a flush boundary past the
        #: cap the file rotates ONCE to `<path>.1` (previous rotation
        #: overwritten) — a long-lived serve daemon must not grow its
        #: append-only JSONL without bound. None/0 = unbounded
        self.flight_max_mb: Optional[float] = _float(
            "TPU_PBRT_FLIGHT_MAX_MB", None
        )
        #: exemplars retained per histogram series (tpu-scope): the
        #: top-K observations by value, each carrying the trace/span ids
        #: the caller attached — the join key from a slow percentile to
        #: the exact trace span that produced it. 0 disables retention
        self.metrics_exemplars: int = _int("TPU_PBRT_METRICS_EXEMPLARS", 4)
        #: health watchdog wedge threshold: the service is flagged
        #: wedged when runnable jobs exist but no chunk-slice has been
        #: dispatched OR retired across this many consecutive step()
        #: calls (obs/health.py)
        self.health_wedge_steps: int = _int("TPU_PBRT_HEALTH_WEDGE_STEPS", 12)
        #: serve SLO admission control (ISSUE 10 / ROADMAP #2 load
        #: shedding): per-priority-class queue-DEPTH targets — a submit
        #: that would push the class's runnable-job count past its target
        #: is answered with a deterministic `shed` instead of queued.
        #: Spec grammar: "8" (every class) or "0=4,5=32" (per class int,
        #: `default=` for the rest); empty = no depth shedding
        self.serve_slo_depth: str = os.environ.get(
            "TPU_PBRT_SERVE_SLO_DEPTH", ""
        ).strip()
        #: ... and per-class queue-WAIT targets in seconds: shed while
        #: the class has queued work AND its recent p90 queue wait (a
        #: bounded in-service window — deliberately NOT the registry's
        #: lifetime histogram, whose p90 could never recover once
        #: elevated) exceeds the target. Same spec grammar
        self.serve_slo_wait_s: str = os.environ.get(
            "TPU_PBRT_SERVE_SLO_WAIT_S", ""
        ).strip()
        #: declarative fault-injection plan (tpu_pbrt/chaos grammar, e.g.
        #: "dispatch:poison@chunk=3,ckpt:torn@write=2"); empty = no chaos.
        #: Installed into the CHAOS registry once at chaos-package import
        #: (snapshot contract — reload() does not re-install)
        self.faults: str = os.environ.get("TPU_PBRT_FAULTS", "").strip()
        #: non-finite film firewall mode: "scrub" (default — NaN/Inf
        #: deposits zeroed + counted in nonfinite_deposits), "raise"
        #: (abort the render on the first scrubbed chunk), "retry"
        #: (treat the chunk as state-poisoned and re-dispatch it exactly;
        #: raise/retry pay a per-chunk device sync for the check and
        #: REQUIRE the telemetry counters — render() rejects the
        #: combination with TPU_PBRT_TELEMETRY=0 rather than silently
        #: degrading to scrub)
        nf = os.environ.get("TPU_PBRT_NONFINITE", "").strip().lower()
        self.nonfinite: str = nf if nf in ("scrub", "raise", "retry") else "scrub"
        #: re-dispatch attempts per chunk before the render gives up
        #: (writes an emergency checkpoint first when one is configured)
        self.retry_max: int = _int("TPU_PBRT_RETRY_MAX", 8)
        #: exponential re-dispatch backoff: base seconds ...
        self.retry_backoff: float = _float("TPU_PBRT_RETRY_BACKOFF", 0.25)
        #: ... and ceiling seconds (attempt k sleeps
        #: min(base * 2^(k-1), cap) * deterministic-jitter[0.5, 1.0])
        self.retry_backoff_cap: float = _float(
            "TPU_PBRT_RETRY_BACKOFF_CAP", 30.0
        )
        #: wall-clock seconds spent retrying before giving up regardless
        #: of the attempt budget — a tight retry loop against a hung
        #: backend once burned a whole capture (0 disables)
        self.retry_deadline: float = _float(
            "TPU_PBRT_RETRY_DEADLINE_S", 600.0
        )
        return self


#: the process-wide snapshot, read once at import
cfg = Config()._load()


def reload() -> Config:
    """Re-read the environment into the existing `cfg` object (same
    identity, so `from tpu_pbrt.config import cfg` holders see the new
    values). Test-only seam."""
    return cfg._load()


def coordinator_address() -> Optional[str]:
    """JAX_COORDINATOR_ADDRESS at CALL time. Unlike the TPU_PBRT_*
    knobs, this standard JAX cluster variable is routinely exported by
    launch drivers after import (post cluster discovery), so the
    import-time snapshot contract does not apply to it."""
    return os.environ.get("JAX_COORDINATOR_ADDRESS") or cfg.coordinator_address


def place_compile_cache() -> str:
    """Place JAX's persistent compilation cache; every entry point calls
    this before its first jit. Where JAX_COMPILATION_CACHE_DIR is set,
    jax reads it itself and nothing is set here, so an operator (or a
    machine that keeps a cache between runs) decides the place;
    otherwise the cache goes to `<checkout>/.jax_cache`. The path is
    part of the cache key's environment, so it is fixed: no temporary
    names, pids or times. Returns the directory in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
