"""tpu-metrics (ISSUE 10): the host-side metrics registry.

Oracles:

- DETERMINISM: fixed-bucket histograms make snapshot/exposition a pure
  function of the observed values — two registries fed the same events
  expose identical bytes, and the bucket-derived p50/p90/p99 are exact
  arithmetic, pinned against hand-computed expectations.
- VALIDATION: the Prometheus text lint accepts the registry's own
  output and rejects the drift classes that break scrapers (missing
  TYPE, broken label escaping, non-monotone cumulative buckets).
- KILL SWITCH: TPU_PBRT_METRICS=0 leaves render stats and images
  byte-identical to a build without the registry, and records nothing.
- SLO: the shed decision is a pure function over (class, depth, p90) —
  a decision table, no service needed.
- SATELLITES: flight-recorder rotation cap, trace-span folding,
  bench_report schema gate over the committed captures.
"""

import json
import os

import numpy as np
import pytest

from tpu_pbrt import config
from tpu_pbrt.obs.metrics import (
    METRICS,
    MetricsRegistry,
    fold_trace,
    percentile_from_buckets,
    phase_summary,
    validate_exposition,
    validate_snapshot,
)
from tpu_pbrt.serve.queue import SloPolicy, parse_slo_spec


def _render_cornell(**kw):
    from tpu_pbrt.scenes import compile_api, make_cornell

    api = make_cornell(res=16, spp=4, integrator="path", maxdepth=3, **kw)
    scene, integ = compile_api(api)
    return scene, integ


# ---------------------------------------------------------------------------
# registry core: determinism + percentile math
# ---------------------------------------------------------------------------


class TestRegistry:
    def _fill(self, reg):
        h = reg.histogram("t_seconds", "latencies", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.06, 0.5, 2.0):
            h.observe(v, tenant="alice", job="j1")
        h.observe(0.05, tenant='bo"b\\x', job="j2")
        c = reg.counter("events_total", "events")
        c.inc(3, kind="a")
        c.inc(kind="b")
        reg.gauge("depth", "queue depth").set(4, priority="0")
        return reg

    def test_snapshot_and_exposition_deterministic(self):
        a = self._fill(MetricsRegistry())
        b = self._fill(MetricsRegistry())
        assert a.exposition() == b.exposition()
        assert a.snapshot() == b.snapshot()
        # and insertion ORDER does not matter: label keys are canonical
        c = MetricsRegistry()
        h = c.histogram("t_seconds", "latencies", buckets=(0.01, 0.1, 1.0))
        h.observe(0.05, job="j2", tenant='bo"b\\x')  # kwargs reordered
        for v in (0.005, 0.05, 0.06, 0.5, 2.0):
            h.observe(v, job="j1", tenant="alice")
        cc = c.counter("events_total", "events")
        cc.inc(kind="b")
        cc.inc(3, kind="a")
        c.gauge("depth", "queue depth").set(4, priority="0")
        assert c.exposition() == a.exposition()

    def test_own_exposition_and_snapshot_validate(self):
        reg = self._fill(MetricsRegistry())
        assert validate_exposition(reg.exposition()) == []
        assert validate_snapshot(reg.snapshot()) == []

    def test_counter_rejects_decrement_and_kind_conflict(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total")
        with pytest.raises(ValueError, match="decremented"):
            c.inc(-1)
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")

    def test_histogram_rejects_edge_conflict(self):
        """Two sites re-registering one histogram with different edges
        must raise — silently sharing the first site's buckets would
        funnel the second site's scale into +Inf."""
        reg = MetricsRegistry()
        h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
        assert reg.histogram("h_seconds", buckets=(0.1, 1.0)) is h
        with pytest.raises(ValueError, match="edges"):
            reg.histogram("h_seconds", buckets=(1.0, 2.0))

    def test_window_p90_nearest_rank(self):
        """The wait-SLO window percentile is nearest-rank: 2 outliers in
        a window of 20 must NOT decide the p90."""
        from tpu_pbrt.serve.service import _window_p90

        assert _window_p90([]) is None
        assert _window_p90([0.3]) == 0.3
        w = [0.1] * 18 + [10.0, 10.0]
        assert _window_p90(w) == 0.1  # rank ceil(18)=18 of 20
        assert _window_p90([0.1] * 17 + [10.0] * 3) == 10.0

    def test_percentiles_from_buckets_exact(self):
        # counts [1,1,1,1] over edges (1,2,4): hand-computed quantiles
        edges = (1.0, 2.0, 4.0)
        counts = [1, 1, 1, 1]
        assert percentile_from_buckets(edges, counts, 0.25) == 1.0
        assert percentile_from_buckets(edges, counts, 0.5) == 2.0
        assert percentile_from_buckets(edges, counts, 0.75) == 4.0
        # the +Inf bucket clamps to the last finite edge
        assert percentile_from_buckets(edges, counts, 0.99) == 4.0
        assert percentile_from_buckets(edges, [0, 0, 0, 0], 0.5) is None
        # interpolation inside a bucket: 10 values in (1, 2]
        assert percentile_from_buckets(
            edges, [0, 10, 0, 0], 0.5
        ) == pytest.approx(1.5)

    def test_histogram_percentile_label_match(self):
        reg = MetricsRegistry()
        h = reg.histogram("w", buckets=(1.0, 2.0))
        for _ in range(4):
            h.observe(0.5, priority="0", tenant="a")
        for _ in range(4):
            h.observe(1.5, priority="1", tenant="b")
        assert h.percentile(0.9, match={"priority": "0"}) <= 1.0
        assert h.percentile(0.9, match={"priority": "1"}) > 1.0
        # subset semantics: {} aggregates everything
        assert h.percentile(0.5, match={}) is not None

    def test_kill_switch_records_nothing(self, monkeypatch):
        monkeypatch.setenv("TPU_PBRT_METRICS", "0")
        config.reload()
        reg = self._fill(MetricsRegistry())
        assert reg.exposition() == ""
        assert reg.snapshot()["metrics"]["tpu_pbrt_events_total"][
            "series"
        ] == []


# ---------------------------------------------------------------------------
# exposition lint: the drift classes that break a scraper
# ---------------------------------------------------------------------------


class TestExpositionLint:
    def test_missing_type_line(self):
        assert validate_exposition("foo 1\n")

    def test_bad_label_escaping(self):
        text = (
            "# TYPE m counter\n"
            'm{a="unescaped"quote"} 1\n'
        )
        assert any("label" in e for e in validate_exposition(text))

    def test_escaped_labels_accepted(self):
        text = (
            "# TYPE m counter\n"
            'm{a="back\\\\slash \\"quote\\" \\nnl"} 1\n'
        )
        assert validate_exposition(text) == []

    def test_non_monotone_buckets(self):
        text = (
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 5\n'
            'h_bucket{le="2"} 3\n'
            'h_bucket{le="+Inf"} 5\n'
            "h_sum 7\n"
            "h_count 5\n"
        )
        assert any("monotone" in e for e in validate_exposition(text))

    def test_count_must_match_inf_bucket(self):
        text = (
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 2\n'
            'h_bucket{le="+Inf"} 2\n'
            "h_sum 1\n"
            "h_count 9\n"
        )
        assert any("_count" in e for e in validate_exposition(text))

    def test_missing_inf_bucket(self):
        text = (
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 2\n'
            "h_sum 1\n"
            "h_count 2\n"
        )
        assert any("+Inf" in e for e in validate_exposition(text))

    def test_snapshot_validator_rejects_drift(self):
        assert validate_snapshot({"schema": "nope"})
        doc = {
            "schema": "tpu-pbrt-metrics-v1",
            "metrics": {
                "m": {"type": "histogram", "help": "", "series": [{
                    "labels": {}, "buckets": ["1", "+Inf"],
                    "counts": [1], "sum": 1.0, "count": 1,
                }]},
            },
        }
        assert any("counts" in e for e in validate_snapshot(doc))


# ---------------------------------------------------------------------------
# SLO shed decision table (pure policy, no service)
# ---------------------------------------------------------------------------


class TestSloPolicy:
    def test_parse_spec(self):
        assert parse_slo_spec("8", int) == {None: 8}
        assert parse_slo_spec("0=4, 5=32", int) == {0: 4, 5: 32}
        assert parse_slo_spec("default=2,1=3", float) == {None: 2.0, 1: 3.0}
        assert parse_slo_spec("", int) == {}
        with pytest.raises(ValueError):
            parse_slo_spec("x=y", int)

    def test_decision_table(self):
        p = SloPolicy(
            depth=parse_slo_spec("default=2,5=10", int),
            wait_s=parse_slo_spec("0=0.5", float),
        )
        table = [
            # (priority, depth, wait_p90, admit?)
            (0, 0, None, True),
            (0, 1, None, True),
            (0, 2, None, False),  # at the default depth target
            (5, 9, None, True),  # class-5 override
            (5, 10, None, False),
            (0, 0, 0.4, True),
            (0, 0, 0.6, False),  # wait breach
            (3, 0, 99.0, True),  # class 3 has no wait target
            (0, 99, None, False),
        ]
        for prio, depth, p90, want in table:
            ok, reason = p.admit(prio, depth, p90)
            assert ok is want, (prio, depth, p90, reason)
            assert ok == (reason == "")

    def test_disabled_policy_admits_everything(self):
        p = SloPolicy()
        assert not p.enabled()
        assert p.admit(0, 10_000, 1e9) == (True, "")

    def test_deterministic_burst(self):
        """The same burst against the same policy sheds the same
        requests — admission is a pure function, twice."""
        def run():
            p = SloPolicy(depth={None: 3})
            out = []
            depth = 0
            for _ in range(6):
                ok, _ = p.admit(0, depth)
                out.append(ok)
                depth += 1 if ok else 0
            return out

        assert run() == run() == [True, True, True, False, False, False]


# ---------------------------------------------------------------------------
# trace-span folding (the offline half of phase attribution)
# ---------------------------------------------------------------------------


class TestFoldTrace:
    def _doc(self):
        ev = []
        for i, dur_us in enumerate((2e6, 3e6, 4e6)):
            ev.append({
                "name": "render/chunk_dispatch", "ph": "X", "ts": i * 1e6,
                "dur": dur_us, "pid": 0, "tid": 0,
                "args": {"chunk": i},
            })
        ev.append({
            "name": "render/develop", "ph": "X", "ts": 9e6, "dur": 1e5,
            "pid": 0, "tid": 0, "args": {},
        })
        ev.append({"name": "unrelated", "ph": "i", "ts": 0, "pid": 0,
                   "tid": 0, "s": "p"})
        return {"traceEvents": ev}

    def test_fold_labels_by_phase(self):
        reg = MetricsRegistry()
        assert fold_trace(self._doc(), reg) == 4
        assert fold_trace(self._doc(), reg) == 4
        summ = phase_summary(reg)
        assert set(summ) == {"dispatch", "deposit_develop"}
        assert summ["dispatch"]["count"] == 6
        h = reg.histogram("render_phase_seconds")
        # one series a phase: `phase` is the histogram's only label
        assert sorted(tuple(dict(k).items()) for k in h._series) == [
            (("phase", "deposit_develop"),), (("phase", "dispatch"),)
        ]
        disp = h.aggregate(match={"phase": "dispatch"})
        assert disp["count"] == 6
        assert disp["seconds"] == pytest.approx(18.0)

    def test_fold_from_file(self, tmp_path):
        p = tmp_path / "trace.json"
        p.write_text(json.dumps(self._doc()))
        reg = MetricsRegistry()
        assert fold_trace(str(p), reg) == 4


# ---------------------------------------------------------------------------
# render-loop phase attribution + the kill-switch bit-identity acceptance
# ---------------------------------------------------------------------------


class TestRenderPhases:
    def test_phase_attribution_and_kill_switch(self, monkeypatch):
        monkeypatch.setenv("TPU_PBRT_METRICS", "1")
        config.reload()
        METRICS.reset()
        scene, integ = _render_cornell()
        r_on = integ.render(scene)
        ph = r_on.stats.get("phase_seconds")
        assert ph, "metrics-on render must report phase attribution"
        assert "dispatch_compile" in ph or "dispatch" in ph
        assert "deposit_develop" in ph
        summ = phase_summary()
        assert summ and all(v["count"] >= 1 for v in summ.values())
        # the registry's own exposition lints clean
        assert validate_exposition(METRICS.exposition()) == []
        # the inline attribution is labelled by phase alone
        h = METRICS.histogram("render_phase_seconds")
        assert all(set(dict(k)) == {"phase"} for k in h._series)

        monkeypatch.setenv("TPU_PBRT_METRICS", "0")
        config.reload()
        METRICS.reset()
        r_off = integ.render(scene)
        # acceptance: the kill switch pins bit-identical stats + image
        assert "phase_seconds" not in r_off.stats
        on_stats = dict(r_on.stats)
        on_stats.pop("phase_seconds")
        assert on_stats == r_off.stats
        assert np.array_equal(np.asarray(r_on.image), np.asarray(r_off.image))
        assert METRICS.exposition() == ""


# ---------------------------------------------------------------------------
# flight-recorder growth cap (satellite)
# ---------------------------------------------------------------------------


class TestFlightRotation:
    def test_rotates_once_past_cap(self, tmp_path, monkeypatch):
        from tpu_pbrt.obs.flight import FlightRecorder, validate_flight

        monkeypatch.setenv("TPU_PBRT_FLIGHT_MAX_MB", "0.0002")  # 200 bytes
        config.reload()
        p = str(tmp_path / "flight.jsonl")
        fr = FlightRecorder()
        fr.configure(p)
        for i in range(20):
            fr.heartbeat("render", chunk=i, payload="x" * 40)
        assert os.path.exists(p + ".1"), "no rotation happened"
        assert os.path.getsize(p) < 3 * 200, "live file grew past the cap"
        # both halves stay valid JSONL and no line was torn
        assert validate_flight(p) == []
        assert validate_flight(p + ".1") == []
        n = sum(
            len(open(f).read().splitlines()) for f in (p, p + ".1")
        )
        assert n >= 4  # older lines beyond one rotation are dropped

    def test_unbounded_by_default(self, tmp_path, monkeypatch):
        from tpu_pbrt.obs.flight import FlightRecorder

        monkeypatch.delenv("TPU_PBRT_FLIGHT_MAX_MB", raising=False)
        config.reload()
        p = str(tmp_path / "flight.jsonl")
        fr = FlightRecorder()
        fr.configure(p)
        for i in range(50):
            fr.heartbeat("render", chunk=i)
        assert not os.path.exists(p + ".1")
        assert len(open(p).read().splitlines()) == 50


# ---------------------------------------------------------------------------
# bench_report (satellite): trajectory table + schema gate
# ---------------------------------------------------------------------------


def _bench_report():
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_report", os.path.join(root, "tools", "bench_report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, root


def _captures(tmp_path):
    """Three capture files in the committed wrapper shape: a run whose
    line did not parse, a measured run, and an unreachable-backend run
    (the repo commits none at present — PR 21 — so the fixtures are
    inline; the numbers are made up)."""
    line = {"metric": "killeroo_like_path_mray_per_sec", "unit": "Mray/s"}
    docs = {
        1: {"n": 1, "cmd": "python bench.py", "rc": 1, "parsed": None},
        3: {"n": 3, "cmd": "python bench.py", "rc": 0, "parsed": {
            **line, "value": 1.25, "vs_baseline": 0.0125,
            "completed_fraction": 1.0, "mse_vs_cpu_ref": 7.1e-05}},
        5: {"n": 5, "cmd": "python bench.py", "rc": 2, "parsed": {
            **line, "value": 0.0, "vs_baseline": 0.0, "infra_outage": True,
            "error": "accelerator backend unreachable (probe hung)"}},
    }
    paths = {}
    for n, doc in docs.items():
        p = tmp_path / f"BENCH_r{n:02d}.json"
        p.write_text(json.dumps(doc))
        paths[n] = str(p)
    return paths


class TestBenchReport:
    def test_captures_pass_schema_gate(self, tmp_path, capsys):
        br, _ = _bench_report()
        files = sorted(_captures(tmp_path).values())
        assert br.main(files) == 0
        table = capsys.readouterr().out
        assert "| r03 | 1.25 |" in table  # the measured row
        assert "r05" in table

    def test_no_committed_capture_is_not_drift(self, capsys, monkeypatch,
                                               tmp_path):
        br, _ = _bench_report()
        monkeypatch.setattr(br, "REPO", str(tmp_path))  # an empty set
        assert br.main([]) == 0
        assert "no BENCH_r*.json" in capsys.readouterr().out

    def test_rows_carry_outage_and_trajectory_fields(self, tmp_path):
        br, _ = _bench_report()
        paths = _captures(tmp_path)
        rows = [br.load_capture(paths[i]) for i in (1, 3, 5)]
        assert rows[0]["outage"] and rows[0]["mray_per_sec"] is None
        assert rows[1]["mray_per_sec"] == 1.25 and not rows[1]["outage"]
        assert rows[2]["outage"] is True
        for row in rows:
            for k in ("run", "roofline", "overlap", "flight_phase"):
                assert k in row

    def test_schema_drift_exits_nonzero(self, tmp_path, capsys):
        br, _ = _bench_report()
        bad = tmp_path / "BENCH_r99.json"
        bad.write_text(json.dumps({"n": 99, "cmd": "x", "rc": 0,
                                   "parsed": {"value": 1.0}}))
        assert br.main([str(bad)]) == 1
        assert "SCHEMA DRIFT" in capsys.readouterr().err

    def test_json_mode(self, tmp_path, capsys):
        br, _ = _bench_report()
        assert br.main([_captures(tmp_path)[3], "--json"]) == 0
        rows = json.loads(capsys.readouterr().out)
        assert rows[0]["run"] == "r03"
