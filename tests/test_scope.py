"""tpu-scope (ISSUE 15): request tracing + timeline reconstruction,
the health watchdog, the bench regression gate, and the per-job flight
rotation cap.

The acceptance scenario lives in TestScopeReconstruction: a DEPTH-2
pipelined serve run with tracing and the flight recorder armed, a
preempt/resume cycle, and a chaos `dispatch:poison` landing mid-window
— `tools/scope.py --check` must rebuild every job's causal timeline
from the exported trace + per-job flight files and find it complete
(paired job/wait/slice spans, bound flow arrows, ok-retired coverage
of every chunk, flight heartbeats joined by trace id).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpu_pbrt import config
from tpu_pbrt.obs import health
from tpu_pbrt.obs.flight import FlightRecorder, job_flight_path
from tpu_pbrt.obs.metrics import MetricsRegistry
from tpu_pbrt.obs.trace import TRACE, TraceRecorder, validate_trace
from tpu_pbrt.scene.api import Options, compile_string
from tpu_pbrt.scenes import cornell_box_text
from tpu_pbrt.serve.service import RenderService

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TEXT = cornell_box_text(res=32, spp=1, integrator="path", maxdepth=3)
CHUNK = 256  # 32*32*1 = 1024 work items -> 4 chunk-slices per job


def _ev(ph, name="n", ts=0.0, **extra):
    ev = {"name": name, "ph": ph, "ts": ts, "pid": 0, "tid": 0, "args": {}}
    if ph == "X":
        ev.setdefault("dur", 1.0)
    ev.update(extra)
    return ev


# --------------------------------------------------------------------------
# trace validator: async pairing, flow binding, overlap attribution
# --------------------------------------------------------------------------


class TestAsyncTraceValidator:
    def test_recorder_roundtrip_validates_clean(self, tmp_path):
        rec = TraceRecorder()
        rec.configure(str(tmp_path / "t.json"))
        tid = rec.trace_id("j1")
        assert tid == "t:j1"
        rec.async_begin("serve/job", id=tid, cat="job", job="j1")
        with rec.async_span("serve/queue_wait", id=f"{tid}/q1", cat="queue"):
            pass
        rec.flow_start("slice_flow", id=f"{tid}/c0")
        rec.flow_finish("slice_flow", id=f"{tid}/c0")
        rec.complete("serve/backoff", 1234.5, chunk=0)
        rec.async_end("serve/job", id=tid, cat="job", outcome="done")
        p = rec.export()
        assert validate_trace(p) == []
        doc = json.load(open(p))
        fin = [e for e in doc["traceEvents"] if e["ph"] == "f"]
        assert fin and fin[0]["bp"] == "e", (
            "flow finish must bind to the enclosing slice (bp=e)"
        )

    def test_unpaired_async_begin_rejected(self):
        doc = {"traceEvents": [
            _ev("b", "serve/job", id="t:j1", cat="job"),
        ]}
        errs = validate_trace(doc)
        assert errs and "never ended" in errs[0]

    def test_async_end_without_begin_rejected(self):
        doc = {"traceEvents": [
            _ev("e", "serve/job", id="t:j1", cat="job"),
        ]}
        errs = validate_trace(doc)
        assert errs and "without an open begin" in errs[0]

    def test_flow_finish_without_start_rejected(self):
        doc = {"traceEvents": [
            _ev("f", "slice_flow", id="t:j1/c0", cat="flow", bp="e"),
        ]}
        errs = validate_trace(doc)
        assert errs and "without a matching flow start" in errs[0]

    def test_unfinished_flow_rejected(self):
        doc = {"traceEvents": [
            _ev("s", "slice_flow", id="t:j1/c0", cat="flow"),
        ]}
        errs = validate_trace(doc)
        assert errs and "never finished" in errs[0]

    def test_async_event_requires_cat_and_id(self):
        errs = validate_trace({"traceEvents": [_ev("b", "x")]})
        assert any("without a cat" in e for e in errs)
        assert any("without an id" in e for e in errs)

    def test_overlapping_slices_without_ahead_rejected(self):
        """The satellite's exact gap: a depth-2 trace whose in-flight
        slice spans overlap but which carries no *_ahead
        dispatch-attribution span anywhere."""
        overlap = [
            _ev("b", "serve/slice_inflight", id="t:a/c0", cat="slice", ts=0),
            _ev("b", "serve/slice_inflight", id="t:a/c1", cat="slice", ts=5),
            _ev("e", "serve/slice_inflight", id="t:a/c0", cat="slice", ts=10),
            _ev("e", "serve/slice_inflight", id="t:a/c1", cat="slice", ts=15),
        ]
        errs = validate_trace({"traceEvents": overlap})
        assert errs and "_ahead" in errs[0]
        ok = overlap + [_ev("X", "serve/dispatch_ahead", ts=5, dur=2.0)]
        assert validate_trace({"traceEvents": ok}) == []

    def test_sequential_slices_need_no_ahead(self):
        """Depth-1 (non-overlapping) slices are fine without any
        lookahead attribution — the check keys on actual overlap."""
        doc = {"traceEvents": [
            _ev("b", "render/slice", id="t:a/c0", cat="slice", ts=0),
            _ev("e", "render/slice", id="t:a/c0", cat="slice", ts=10),
            _ev("b", "render/slice", id="t:a/c1", cat="slice", ts=10),
            _ev("e", "render/slice", id="t:a/c1", cat="slice", ts=20),
        ]}
        assert validate_trace(doc) == []


# --------------------------------------------------------------------------
# health watchdog conditions (pure units)
# --------------------------------------------------------------------------


class _FakeJob:
    def __init__(self, status="queued", attempt=0, job_id="j1"):
        self.status = status
        self.attempt = attempt
        self.job_id = job_id


class _FakeService:
    def __init__(self, jobs=(), steps=0, progress=0, sheds=0, seq=0):
        self.jobs = {j.job_id: j for j in jobs}
        self.health_steps = steps
        self.last_progress_step = progress
        self.sheds = sheds
        self._seq = seq


class TestHealthWatchdog:
    def _reg(self):
        return MetricsRegistry(force_enabled=True)

    def test_wedge_fires_on_stuck_runnable_work(self):
        svc = _FakeService([_FakeJob("queued")], steps=20, progress=2)
        rep = health.evaluate(svc, self._reg(),
                              health.Thresholds(wedge_steps=12))
        assert "wedge" in rep.firing()

    def test_wedge_silent_without_runnable_jobs(self):
        """A long idle gap with every job terminal/paused is not a
        wedge — there is nothing to make progress ON."""
        svc = _FakeService([_FakeJob("done")], steps=100, progress=0)
        rep = health.evaluate(svc, self._reg(),
                              health.Thresholds(wedge_steps=12))
        assert rep.ok

    def test_wedge_silent_under_threshold(self):
        svc = _FakeService([_FakeJob("queued")], steps=11, progress=0)
        rep = health.evaluate(svc, self._reg(),
                              health.Thresholds(wedge_steps=12))
        assert "wedge" not in rep.firing()

    def test_backoff_storm_fires_on_live_retry_streak(self):
        svc = _FakeService([_FakeJob("parked", attempt=3)], steps=1)
        rep = health.evaluate(svc, self._reg())
        assert "backoff_storm" in rep.firing()
        # attempt resets on success: the same job post-recovery is clean
        svc2 = _FakeService([_FakeJob("active", attempt=0)], steps=1)
        assert health.evaluate(svc2, self._reg()).ok

    def test_slo_burn_needs_fraction_and_floor(self):
        reg = self._reg()
        reg.counter("serve_shed_total", "sheds").inc(4, tenant="a")
        reg.counter("serve_submits_total", "admits").inc(2, tenant="a")
        rep = health.evaluate(None, reg)
        assert "slo_burn" in rep.firing()
        # 2 sheds of 4: over 50%? no — exactly 50% with floor unmet
        reg2 = self._reg()
        reg2.counter("serve_shed_total", "sheds").inc(2, tenant="a")
        reg2.counter("serve_submits_total", "admits").inc(2, tenant="a")
        assert health.evaluate(None, reg2).ok

    def test_slo_burn_falls_back_to_service_counts(self):
        """Registry armed but empty (metrics enabled after the fact):
        the service's own deterministic counts carry the signal."""
        svc = _FakeService(sheds=5, seq=1)
        rep = health.evaluate(svc, self._reg())
        assert "slo_burn" in rep.firing()

    def test_nonfinite_spike(self):
        reg = self._reg()
        reg.counter(
            "render_nonfinite_total", "scrubbed deposits"
        ).inc(7, tenant="a")
        rep = health.evaluate(None, reg)
        assert "nonfinite_spike" in rep.firing()
        cond = {c.name: c for c in rep.conditions}["nonfinite_spike"]
        assert cond.value == 7.0

    def test_snapshot_evaluation_matches_registry(self):
        reg = self._reg()
        reg.counter("serve_shed_total", "sheds").inc(4, tenant="a")
        reg.counter("serve_submits_total", "admits").inc(1, tenant="a")
        reg.counter(
            "render_nonfinite_total", "scrubbed deposits"
        ).inc(2, tenant="a")
        live = health.evaluate(None, reg)
        snap = health.evaluate_snapshot(reg.snapshot())
        assert live.firing() == snap.firing() == [
            "slo_burn", "nonfinite_spike",
        ]

    def test_report_shape(self):
        d = health.evaluate(None, self._reg()).to_dict()
        assert d["ok"] is True and d["firing"] == []
        assert sorted(c["name"] for c in d["conditions"]) == [
            "backoff_storm", "nonfinite_spike", "slo_burn", "wedge",
        ]


# --------------------------------------------------------------------------
# per-job flight rotation cap (satellite a)
# --------------------------------------------------------------------------


class TestJobFlightRotation:
    def test_job_heartbeat_rotates_at_cap(self, tmp_path, monkeypatch):
        """The TPU_PBRT_FLIGHT_MAX_MB cap must govern per-job files
        written through job_heartbeat — the pre-fix service re-armed
        `_path` per heartbeat and the cap applied only as a side effect
        of that swap."""
        monkeypatch.setenv("TPU_PBRT_FLIGHT_MAX_MB", "0.001")  # 1000 B
        config.reload()
        base = str(tmp_path / "flight.jsonl")
        fr = FlightRecorder()
        fr.configure(base)
        for i in range(40):  # ~100 B/line: several rotations
            fr.job_heartbeat("j1", "serve_slice", chunk=i, pad="x" * 60)
        per_job = job_flight_path(base, "j1")
        assert os.path.exists(per_job) and os.path.exists(per_job + ".1")
        assert os.path.getsize(per_job) < 2000
        assert os.path.getsize(per_job + ".1") < 2000
        assert not os.path.exists(base), (
            "job heartbeats must land in the per-job file only"
        )

    def test_job_heartbeat_disabled_writes_nothing(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("TPU_PBRT_TELEMETRY", "0")
        config.reload()
        base = str(tmp_path / "flight.jsonl")
        fr = FlightRecorder()
        fr.configure(base)
        fr.job_heartbeat("j1", "serve_slice", chunk=0)
        assert fr.last_phase == "serve_slice"
        assert not os.listdir(tmp_path)


# --------------------------------------------------------------------------
# bench regression gate (satellite + tentpole layer 3)
# --------------------------------------------------------------------------


class TestBenchGate:
    def test_selftest_and_named_regression(self):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_gate.py"),
             "--selftest"],
            capture_output=True, text=True, cwd=REPO, timeout=60,
        )
        assert r.returncode == 0, r.stdout + r.stderr

    def test_fresh_regression_exits_nonzero_naming_metric(self, tmp_path):
        # an inline baseline capture (none is committed at present)
        base = {"metric": "killeroo_like_path_mray_per_sec", "value": 1.25,
                "unit": "Mray/s", "vs_baseline": 0.0125}
        with open(tmp_path / "BENCH_r03.json", "w") as f:
            json.dump({"n": 3, "cmd": "python bench.py", "rc": 0,
                       "parsed": base}, f)
        slow = dict(base)
        slow["value"] = base["value"] * 0.5
        p = str(tmp_path / "fresh.json")
        json.dump(slow, open(p, "w"))
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_gate.py"), p,
             "--baseline-glob", str(tmp_path / "BENCH_r*.json")],
            capture_output=True, text=True, cwd=REPO, timeout=60,
        )
        assert r.returncode == 1
        assert "value regressed" in r.stderr

    def test_outage_capture_exempt(self, tmp_path):
        p = str(tmp_path / "outage.json")
        json.dump({"value": 0.0, "error": "backend gone"}, open(p, "w"))
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_gate.py"), p],
            capture_output=True, text=True, cwd=REPO, timeout=60,
        )
        assert r.returncode == 0
        assert "OUTAGE" in r.stdout


# --------------------------------------------------------------------------
# the acceptance scenario: depth-2 + preempt/resume + chaos poison
# --------------------------------------------------------------------------


class TestScopeReconstruction:
    def _armed_run(self, tmp_path, monkeypatch):
        """Depth-2 pipelined serve drain with tracing + flight armed:
        two tenants, a preempt/resume cycle on j2, and a chaos
        `dispatch:poison` firing mid-window (rollback replay for the
        checkpointed job). Returns (trace path, flight base, job ids)."""
        from tpu_pbrt.chaos import CHAOS

        trace_p = str(tmp_path / "trace.json")
        flight_p = str(tmp_path / "flight.jsonl")
        monkeypatch.setenv("TPU_PBRT_TRACE_PATH", trace_p)
        monkeypatch.setenv("TPU_PBRT_FLIGHT_PATH", flight_p)
        monkeypatch.setenv("TPU_PBRT_PIPELINE", "2")
        monkeypatch.setenv("TPU_PBRT_RETRY_BACKOFF", "0.01")
        config.reload()
        TRACE.reset()
        svc = RenderService(chunk=CHUNK, seed=0)
        opts = Options(quiet=True)
        j1 = svc.submit(
            text=TEXT, tenant="alice",
            checkpoint_path=str(tmp_path / "j1.ckpt"), checkpoint_every=1,
        )
        j2 = svc.submit(text=TEXT, tenant="bob")
        CHAOS.install("dispatch:poison@chunk=2", seed=0)
        try:
            for _ in range(3):
                svc.step()
            svc.preempt(j2)
            for _ in range(2):
                svc.step()
            svc.resume(j2)
            svc.drain()
        finally:
            CHAOS.clear()
        for j in (j1, j2):
            assert svc.jobs[j].status == "done", svc.jobs[j].error
        assert TRACE.export() == trace_p
        TRACE.reset()
        return trace_p, flight_p, (j1, j2)

    def test_depth2_poisoned_run_reconstructs_gap_free(
        self, tmp_path, monkeypatch
    ):
        trace_p, flight_p, jobs = self._armed_run(tmp_path, monkeypatch)
        # the exported trace itself passes the async/flow validator
        assert validate_trace(trace_p) == []
        # and scope.py rebuilds one complete causal timeline per job
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "scope.py"),
             trace_p, "--flight", flight_p, "--check"],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert r.returncode == 0, (
            f"scope --check found defects:\n{r.stdout}\n{r.stderr}"
        )
        assert "2 done" in r.stdout
        # single-job filter + human timeline render
        r2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "scope.py"),
             trace_p, "--flight", flight_p, "--job", jobs[0]],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert r2.returncode == 0, r2.stdout + r2.stderr
        assert f"t:{jobs[0]}" in r2.stdout
        assert "retired ok" in r2.stdout
        # per-job flight lines carry the job's trace id (the join key)
        per_job = flight_p.replace("flight.jsonl", f"flight.{jobs[0]}.jsonl")
        lines = [
            json.loads(x)
            for x in open(per_job).read().splitlines() if x.strip()
        ]
        assert lines and all(
            ln["trace_id"] == f"t:{jobs[0]}" for ln in lines
        )
        phases = {ln["phase"] for ln in lines}
        assert {"serve_submit", "serve_done"} <= phases

    def test_scope_check_catches_a_severed_timeline(
        self, tmp_path, monkeypatch
    ):
        """Adversarial half: drop one slice's retire (async end) event
        from a valid export — scope --check must exit non-zero and name
        the job."""
        trace_p, flight_p, jobs = self._armed_run(tmp_path, monkeypatch)
        doc = json.load(open(trace_p))
        evs = doc["traceEvents"]
        cut = next(
            i for i, e in enumerate(evs)
            if e.get("ph") == "e" and e.get("cat") == "slice"
            and str(e.get("id", "")).startswith(f"t:{jobs[0]}/")
        )
        severed = [e for i, e in enumerate(evs) if i != cut]
        bad_p = str(tmp_path / "severed.json")
        json.dump({"traceEvents": severed}, open(bad_p, "w"))
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "scope.py"),
             bad_p, "--check"],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert r.returncode != 0
        assert jobs[0] in r.stderr or f"t:{jobs[0]}" in r.stderr

    def test_unarmed_run_emits_no_artifacts(self, tmp_path, monkeypatch):
        """With TPU_PBRT_TRACE_PATH unset the whole tpu-scope layer is
        a no-op: no events buffered, no flight files, byte-identical
        render stats path (the contract the ISSUE pins)."""
        monkeypatch.delenv("TPU_PBRT_TRACE_PATH", raising=False)
        monkeypatch.delenv("TPU_PBRT_FLIGHT_PATH", raising=False)
        config.reload()
        TRACE.reset()
        svc = RenderService(chunk=CHUNK, seed=0)
        j = svc.submit(text=TEXT, tenant="alice")
        svc.drain()
        assert svc.jobs[j].status == "done"
        assert TRACE._events == []
        assert TRACE.maybe_export() is None
        assert not [
            f for f in os.listdir(tmp_path) if "flight" in f or "trace" in f
        ]
