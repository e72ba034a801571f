"""tpu-trace telemetry subsystem (ISSUE 4): bit-identity of the render
under the telemetry kill switch, counter-block correctness, zero added
retraces/host-transfers (reusing the jaxpr-audit harness), trace-export
schema validation, flight-recorder format, and the live-vs-static
roofline cross-check."""

import json
import os

import numpy as np
import pytest

from tpu_pbrt import config
from tpu_pbrt.obs import counters as obs_counters
from tpu_pbrt.obs.flight import FlightRecorder, validate_flight
from tpu_pbrt.obs.rooflive import live_vs_static, load_static_budget
from tpu_pbrt.obs.trace import TraceRecorder, validate_trace


def _render_cornell(**kw):
    from tpu_pbrt.scenes import compile_api, make_cornell

    api = make_cornell(res=16, spp=4, integrator="path", maxdepth=3, **kw)
    scene, integ = compile_api(api)
    return integ.render(scene)


# ---------------------------------------------------------------------------
# config seam (ISSUE 4 satellite: knobs through the central config)
# ---------------------------------------------------------------------------


class TestConfigSeam:
    def test_telemetry_default_on_and_kill_switch(self, monkeypatch):
        monkeypatch.delenv("TPU_PBRT_TELEMETRY", raising=False)
        config.reload()
        assert config.cfg.telemetry is True
        monkeypatch.setenv("TPU_PBRT_TELEMETRY", "0")
        config.reload()
        assert config.cfg.telemetry is False
        assert obs_counters.enabled() is False
        assert obs_counters.maybe_zeros() is None

    def test_trace_and_flight_paths_reload(self, monkeypatch):
        monkeypatch.setenv("TPU_PBRT_TRACE_PATH", "/tmp/t.json")
        monkeypatch.setenv("TPU_PBRT_FLIGHT_PATH", "/tmp/f.jsonl")
        config.reload()
        assert config.cfg.trace_path == "/tmp/t.json"
        assert config.cfg.flight_path == "/tmp/f.jsonl"
        monkeypatch.delenv("TPU_PBRT_TRACE_PATH")
        monkeypatch.delenv("TPU_PBRT_FLIGHT_PATH")
        config.reload()
        assert config.cfg.trace_path is None
        assert config.cfg.flight_path is None


# ---------------------------------------------------------------------------
# bit-identity + counter correctness (the tentpole acceptance)
# ---------------------------------------------------------------------------


class TestBitIdentity:
    def test_film_identical_and_counters_consistent(self, monkeypatch):
        """Telemetry ON == telemetry OFF, bit for bit; the counter block
        reconciles exactly with the independent ray/wave accounting."""
        monkeypatch.setenv("TPU_PBRT_TELEMETRY", "1")
        config.reload()
        r_on = _render_cornell()
        tel = r_on.stats["telemetry"]
        ctr = tel["counters"]
        # rays counted by the telemetry block == the judged ray counter
        assert ctr["rays_traced"] == r_on.rays_traced > 0
        # every wave histogrammed exactly once
        assert sum(ctr["occupancy_histogram"]) == r_on.stats["n_waves"]
        # every work item (16*16 px * 4 spp) regenerated, terminated and
        # deposited exactly once on an un-truncated drain
        n_work = 16 * 16 * 4
        assert ctr["lanes_regenerated"] == n_work
        assert ctr["lanes_terminated"] == n_work
        assert ctr["film_deposits"] == n_work
        # single-device spread is degenerate but well-formed
        assert tel["wave_spread"]["per_device_waves"] == [
            r_on.stats["n_waves"]
        ]
        assert tel["wave_spread"]["rel_spread"] == 0.0
        # and so is the ray spread, made on the host from the total the
        # render reports anyway: the one-device program sends nothing out
        assert tel["ray_spread"]["per_device_rays"] == [r_on.rays_traced]
        assert tel["ray_spread"]["rel_spread"] == 0.0

        monkeypatch.setenv("TPU_PBRT_TELEMETRY", "0")
        config.reload()
        r_off = _render_cornell()
        assert "telemetry" not in r_off.stats
        assert np.array_equal(
            np.asarray(r_on.image), np.asarray(r_off.image)
        ), "telemetry changed the rendered image"

    def test_kill_switch_compiles_pre_telemetry_program(self, monkeypatch):
        """TPU_PBRT_TELEMETRY=0 is not a masked variant: the traced pool
        drain has the pre-telemetry output arity (film 3 + nrays + live +
        waves + truncated = 7 avals) and strictly fewer equations."""
        from tpu_pbrt.analysis import audit

        monkeypatch.setenv("TPU_PBRT_TELEMETRY", "1")
        config.reload()
        jx_on = audit.pool_chunk_jaxpr()
        monkeypatch.setenv("TPU_PBRT_TELEMETRY", "0")
        config.reload()
        jx_off = audit.pool_chunk_jaxpr()
        assert len(jx_off.jaxpr.outvars) == 7
        # 14 counter leaves (5 scalars incl. nonfinite_deposits, the
        # occupancy histogram, 7 stream-tracer work scalars with the
        # flush's block slots and EXPAND's pairs put back, the brute
        # tracer's rays)
        assert len(jx_on.jaxpr.outvars) == 21
        n_on = sum(len(j.eqns) for j in audit.iter_jaxprs(jx_on.jaxpr))
        n_off = sum(len(j.eqns) for j in audit.iter_jaxprs(jx_off.jaxpr))
        assert n_off < n_on


class TestNoAddedOverhead:
    """Acceptance: zero extra retraces and zero extra host transfers with
    telemetry on (default) — the jaxpr-audit harness re-run as the gate."""

    def test_zero_retraces_with_telemetry_on(self):
        from tpu_pbrt.analysis import audit

        assert config.cfg.telemetry is True
        assert audit.check_recompile_guard() == []

    def test_transfer_guard_clean_with_telemetry_on(self):
        from tpu_pbrt.analysis import audit

        assert config.cfg.telemetry is True
        assert audit.check_transfer_guard() == []


# ---------------------------------------------------------------------------
# counter host-side algebra
# ---------------------------------------------------------------------------


class TestCounterAlgebra:
    def test_merge_host_sums_and_pads(self):
        a = {"rays_traced": 10, "occupancy_histogram": [1, 2]}
        b = {"rays_traced": 5, "occupancy_histogram": [3, 4, 5],
             "film_deposits": 7}
        m = obs_counters.merge_host(a, b)
        assert m["rays_traced"] == 15
        assert m["occupancy_histogram"] == [4, 6, 5]
        assert m["film_deposits"] == 7
        assert obs_counters.merge_host({}, b) == b
        assert obs_counters.merge_host(a, {}) == a

    def test_spread_stats(self):
        s = obs_counters.spread_stats([10, 20, 10, 40])
        assert s["min"] == 10 and s["max"] == 40 and s["mean"] == 20.0
        assert s["rel_spread"] == pytest.approx(1.5)
        assert obs_counters.spread_stats([]) == {}
        assert s["per_device_waves"] == [10, 20, 10, 40]
        assert obs_counters.spread_stats([3, 5], "rays")["per_device_rays"] == [3, 5]

    def test_spread_telemetry(self):
        # two dispatches' (waves, rays) blocks of a three-device mesh
        blocks = [np.array([[2, 3, 2], [10, 30, 20]]), np.array([[1, 1, 2], [5, 5, 50]])]
        t = obs_counters.spread_telemetry(blocks, 11, 120)
        assert t["wave_spread"]["per_device_waves"] == [3, 4, 4]
        assert t["ray_spread"]["per_device_rays"] == [15, 35, 70]
        assert t["ray_spread"]["rel_spread"] == pytest.approx(55 / 40)
        # no mesh: one entry each, from the host's own totals
        t = obs_counters.spread_telemetry([], 11, 120)
        assert t["wave_spread"]["per_device_waves"] == [11]
        assert t["ray_spread"] == obs_counters.spread_stats([120], "rays")
        # no pool drained: nothing to spread
        assert obs_counters.spread_telemetry([], None, 120) == {
            "wave_spread": {}, "ray_spread": {}}


# ---------------------------------------------------------------------------
# trace recorder: schema validation of the export
# ---------------------------------------------------------------------------


class TestTraceExport:
    def _recorder(self, tmp_path):
        rec = TraceRecorder()
        rec.configure(str(tmp_path / "trace.json"))
        return rec

    def test_export_schema_valid(self, tmp_path):
        rec = self._recorder(tmp_path)
        with rec.span("bench/measure", chunk=3):
            with rec.span("render/chunk_dispatch"):
                pass
        rec.instant("checkpoint")
        rec.counter("occupancy", live=123)
        path = rec.export()
        assert validate_trace(path) == []
        doc = json.loads(open(path).read())
        names = [e["name"] for e in doc["traceEvents"]]
        assert "bench/measure" in names and "occupancy" in names
        # nested span closed after its parent opened: ts ordering holds
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert all(e["dur"] >= 0 for e in spans)

    def test_validator_rejects_malformed(self):
        assert validate_trace({"nope": []})
        assert validate_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "ts": -1}]}
        )
        assert validate_trace(
            {"traceEvents": [{"name": "", "ph": "i", "ts": 0,
                              "pid": 0, "tid": 0}]}
        )
        # a complete span without dur is malformed
        assert validate_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "ts": 0,
                              "pid": 0, "tid": 0}]}
        )

    def test_disabled_recorder_is_noop(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPU_PBRT_TELEMETRY", "0")
        config.reload()
        rec = self._recorder(tmp_path)
        with rec.span("x"):
            pass
        assert rec.maybe_export() is None
        assert not os.path.exists(str(tmp_path / "trace.json"))


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_heartbeats_and_validation(self, tmp_path):
        p = str(tmp_path / "flight.jsonl")
        fr = FlightRecorder()
        fr.configure(p)
        fr.heartbeat("probe", attempt=1, ok=False)
        fr.heartbeat("probe", attempt=2, ok=True)
        fr.heartbeat("measure", chunk=1)
        fr.counters({"rays_traced": 99}, phase="render_done")
        assert fr.last_phase == "render_done"
        assert fr.last_counters == {"rays_traced": 99}
        assert validate_flight(p, require_phases=["probe", "measure",
                                                  "render_done"]) == []
        errs = validate_flight(p, require_phases=["develop"])
        assert errs and "develop" in errs[0]
        lines = [json.loads(x) for x in open(p).read().splitlines()]
        assert lines[0]["phase"] == "probe"
        assert lines[-1]["counters"] == {"rays_traced": 99}

    def test_reserved_keys_win_over_caller_kwargs(self, tmp_path):
        """A phase kwarg named elapsed_s must not clobber the recorder's
        own monotonic baseline field."""
        p = str(tmp_path / "flight.jsonl")
        fr = FlightRecorder()
        fr.configure(p)
        fr.heartbeat("render", elapsed_s=9999.0, chunk=3)
        rec = json.loads(open(p).read().splitlines()[0])
        assert rec["elapsed_s"] < 9999.0
        assert rec["chunk"] == 3

    def test_configure_t0_rebases_elapsed(self, tmp_path):
        """bench hands its probe-phase start time over at the import
        handoff so one JSONL keeps a single monotonic elapsed_s
        baseline (the probe's import-free writer measured from the
        same epoch)."""
        import time

        p = str(tmp_path / "flight.jsonl")
        fr = FlightRecorder()
        fr.configure(p, t0=time.time() - 100.0)
        fr.heartbeat("measure")
        rec = json.loads(open(p).read().splitlines()[0])
        assert rec["elapsed_s"] >= 100.0

    def test_disabled_recorder_tracks_phase_without_writing(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("TPU_PBRT_TELEMETRY", "0")
        config.reload()
        p = str(tmp_path / "flight.jsonl")
        fr = FlightRecorder()
        fr.configure(p)
        fr.heartbeat("measure")
        # the outage JSON still reports last_phase; nothing hits disk
        assert fr.last_phase == "measure"
        assert not os.path.exists(p)

    def test_render_writes_phase_heartbeats(self, tmp_path, monkeypatch):
        """The render loop heartbeats its phases (the CI smoke asserts
        the same through main.py)."""
        from tpu_pbrt.obs.flight import FLIGHT

        p = str(tmp_path / "render_flight.jsonl")
        monkeypatch.setenv("TPU_PBRT_FLIGHT_PATH", p)
        config.reload()
        FLIGHT.configure(None)  # fall through to cfg.flight_path
        try:
            _render_cornell()
        finally:
            FLIGHT.configure(None)
        assert validate_flight(
            p, require_phases=["render", "render_done", "develop"]
        ) == []
        done = [
            json.loads(x) for x in open(p).read().splitlines()
            if json.loads(x)["phase"] == "render_done"
        ]
        assert done and done[-1]["counters"]["rays_traced"] > 0


# ---------------------------------------------------------------------------
# live-vs-static roofline cross-check
# ---------------------------------------------------------------------------


class TestRooflive:
    def test_ratio_null_on_unknown_platform(self):
        out = live_vs_static(
            waves=100, seconds=2.0, static_bytes_per_wave=1_000_000,
            device_kind="cpu",
        )
        assert out["live_bytes_per_sec"] == pytest.approx(5e7)
        assert out["live_vs_static_ratio"] is None

    def test_ratio_on_known_tpu(self):
        out = live_vs_static(
            waves=1000, seconds=1.0,
            static_bytes_per_wave=6_446_032_534,
            static_flops_per_wave=3_834_297_836,
            device_kind="TPU v5e", n_devices=8,
        )
        assert out["hbm_peak_bytes_per_sec"] == pytest.approx(8 * 819e9)
        assert out["live_vs_static_ratio"] == pytest.approx(
            6_446_032_534 * 1000 / (8 * 819e9), rel=1e-6
        )
        assert out["live_flops_per_sec"] == pytest.approx(3.834297836e12)

    def test_unknown_tpu_kind_is_an_error(self):
        with pytest.raises(ValueError, match="unknown TPU device_kind"):
            live_vs_static(
                waves=100, seconds=2.0, static_bytes_per_wave=1_000_000,
                device_kind="TPU v9 hypothetical",
            )
        # the string a v5e reports (chip run, PR 21) is in the table
        out = live_vs_static(
            waves=100, seconds=2.0, static_bytes_per_wave=1_000_000,
            device_kind="TPU v5 lite",
        )
        assert out["hbm_peak_bytes_per_sec"] == pytest.approx(819e9)

    def test_missing_inputs_degrade_to_nulls(self):
        out = live_vs_static(waves=None, seconds=None)
        assert out == {
            "live_bytes_per_sec": None, "live_flops_per_sec": None,
            "hbm_peak_bytes_per_sec": None, "live_vs_static_ratio": None,
        }

    def test_static_budget_fallback_reads_committed_file(self):
        entry = load_static_budget("pool_chunk")
        assert entry.get("hbm_bytes", 0) > 0
        assert load_static_budget("no_such_entry") == {}


# ---------------------------------------------------------------------------
# compile accounting + where the persistent cache goes (ISSUE 21)
# ---------------------------------------------------------------------------


class TestCompiles:
    def test_cache_placement_env_wins_else_checkout(self, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: jax reads it itself and the
        program sets nothing. Unset: <checkout>/.jax_cache."""
        import os

        import jax

        from tpu_pbrt import config

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        here = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache",
        )
        assert config.place_compile_cache() == here
        assert jax.config.jax_compilation_cache_dir == here
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert config.place_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == here  # untouched

    def test_tracker_counts_traces_and_programs(self):
        import jax
        import jax.numpy as jnp

        from tpu_pbrt.obs.compiles import COMPILES, process_report

        COMPILES.install()
        before = (COMPILES.traces, COMPILES.programs)
        f = jax.jit(lambda x: x * 3.0 + 1.0)
        f(jnp.ones((7,), jnp.float32)).block_until_ready()
        assert COMPILES.traces > before[0] and COMPILES.programs > before[1]
        steady = (COMPILES.traces, COMPILES.programs)
        f(jnp.ones((7,), jnp.float32)).block_until_ready()
        assert (COMPILES.traces, COMPILES.programs) == steady
        rep = process_report()
        assert rep["platform"] == "cpu" and rep["device_kind"]
        assert rep["jax"] == jax.__version__ and rep["programs"] == steady[1]
