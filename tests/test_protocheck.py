"""protocheck (ISSUE 17): exhaustive interleaving & fault-schedule
verification of the serve/dispatch protocol (analysis layer 5).

Four pieces under test: the VirtualClock seam (utils/clock.py) that
makes a service run a pure function of a decision sequence, the SV-*
static rules over the protocol modules, the seeded mutation-regression
corpus (each historical bug re-introduced must be flagged BY NAME
through the real `tools/explore.py --mutate` entry point, and the
clean tree must pass the exact same decision sequences), and the
bounded explorer itself — clean-grid search, byte-identical replay
(PROTO-DET), and a virtual-time trace export that `tools/scope.py
--check` accepts.
"""

import importlib.util
import json
import os

import pytest

from tpu_pbrt.analysis import protocheck as pc
from tpu_pbrt.utils.clock import WALL, Clock, VirtualClock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    """Import a tools/ script (not a package) as a throwaway module."""
    spec = importlib.util.spec_from_file_location(
        f"_protocheck_test_{name}", os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def explore():
    return _load_tool("explore")


# ---------------------------------------------------------------------------
# the clock seam
# ---------------------------------------------------------------------------


class TestVirtualClock:
    def test_decision_sample_ticks_peek_does_not(self):
        vc = VirtualClock(start=10.0, tick=0.5)
        assert vc.peek() == 10.0
        assert vc.now() == 10.0  # returns current time, THEN ticks
        assert vc.peek() == 10.5  # the hidden-double-sample detector
        assert vc.now() == 10.5
        assert vc.samples == 2
        assert vc.monotonic() == vc.peek()  # one timeline, no epoch split

    def test_sleep_advances_instead_of_blocking(self):
        vc = VirtualClock()
        vc.sleep(2.0)
        assert vc.peek() == 2.0 and vc.sleeps == 1
        vc.sleep(-5.0)  # negative sleeps clamp like time.sleep rejects
        assert vc.peek() == 2.0

    def test_advance_to_never_goes_backward(self):
        vc = VirtualClock(start=3.0)
        vc.advance_to(1.0)
        assert vc.peek() == 3.0
        vc.advance_to(5.0)
        assert vc.peek() == 5.0
        vc.advance(0.25)
        assert vc.peek() == 5.25

    def test_wall_clock_is_the_default_interface(self):
        assert isinstance(WALL, Clock)
        a = WALL.now()
        assert WALL.peek() >= a  # real time, still ordered


class TestVirtualTimeTelemetry:
    """Satellite: the obs recorders under an injected VirtualClock must
    emit monotone nonnegative stamps and must not perturb the timeline
    (arming telemetry cannot change a virtual run's schedule)."""

    def test_trace_rebases_and_stays_monotone(self, tmp_path):
        from tpu_pbrt.obs.trace import TraceRecorder, validate_trace

        rec = TraceRecorder()
        rec.configure(str(tmp_path / "t.json"))
        vc = VirtualClock(start=100.0)
        rec.set_clock(vc)
        assert rec.clock_kind == "virtual"
        with rec.span("alpha"):
            vc.advance(0.25)
        vc.advance(1.0)
        rec.instant("mark")
        out = rec.export()
        doc = json.loads(open(out).read())
        assert doc["otherData"]["clock"] == "virtual"
        ts = [e["ts"] for e in doc["traceEvents"]]
        # rebase: starts at 0 despite the clock starting at 100 s; a
        # wall _t0 here would produce the negative stamps validate_trace
        # rejects
        assert ts[0] == 0.0 and ts == sorted(ts)
        assert validate_trace(doc) == []
        assert vc.samples == 0  # recording used monotonic(), not now()
        rec.set_clock(None)
        assert rec.clock_kind == "wall"

    def test_flight_heartbeats_monotone_under_virtual_time(self, tmp_path):
        from tpu_pbrt.obs.flight import FlightRecorder

        fr = FlightRecorder()
        fr.configure(str(tmp_path / "f.jsonl"))
        vc = VirtualClock(start=50.0)
        fr.set_clock(vc)
        fr.heartbeat("boot")
        vc.advance(0.5)
        fr.heartbeat("render", chunk=1)
        vc.advance(0.5)
        fr.heartbeat("render", chunk=2)
        lines = [json.loads(x) for x in open(tmp_path / "f.jsonl")]
        assert [x["t"] for x in lines] == sorted(x["t"] for x in lines)
        assert lines[0]["elapsed_s"] == 0.0  # rebased onto the clock
        assert lines[-1]["elapsed_s"] == 1.0
        assert vc.samples == 0  # peek() only: heartbeats never tick
        fr.set_clock(None)


# ---------------------------------------------------------------------------
# SV-* static rules
# ---------------------------------------------------------------------------


def _rules(src, rel):
    return [v.rule for v in pc.sv_lint_source(src, rel)]


class TestSvLint:
    def test_raw_wall_clock_in_scoped_module(self):
        src = "import time\n\ndef f(self):\n    return time.monotonic()\n"
        assert _rules(src, "tpu_pbrt/serve/service.py") == ["SV-CLOCK"]
        # the same call outside the protocol modules is fine
        assert _rules(src, "tpu_pbrt/film/image.py") == []

    def test_double_decision_sample_in_deadline_scope(self):
        src = (
            "def step(self):\n"
            "    now = self._now()\n"
            "    job = self._runnable(now)\n"
            "    later = self._now()\n"
            "    return job, later\n"
        )
        vs = pc.sv_lint_source(src, "tpu_pbrt/serve/service.py")
        assert [v.rule for v in vs] == ["SV-CLOCK"]
        assert "samples the decision clock 2 times" in vs[0].message

    def test_double_sample_outside_deadline_scope_allowed(self):
        # two samples bracketing a span is the TIMING idiom, legal when
        # the function never reasons about deadlines/runnability
        src = "def t(self):\n    a = self._now()\n    b = self._now()\n    return b - a\n"
        assert _rules(src, "tpu_pbrt/serve/service.py") == []

    def test_defer_requires_cursor_binding(self):
        bad = "def q(self, w, fn):\n    w.defer(fn)\n"
        good = "def q(self, w, fn):\n    w.defer(3, fn)\n"
        assert _rules(bad, "tpu_pbrt/serve/service.py") == ["SV-DEFER"]
        assert _rules(good, "tpu_pbrt/serve/service.py") == []

    def test_checkpoint_then_flush_must_discard(self):
        bad = (
            "def park(self, job):\n"
            "    save_checkpoint(job)\n"
            "    job.window.flush()\n"
        )
        good = bad.replace("flush()", "flush(discard=True)")
        vs = pc.sv_lint_source(bad, "tpu_pbrt/serve/service.py")
        assert [v.rule for v in vs] == ["SV-DEFER"]
        assert "superseded cursor" in vs[0].message
        assert _rules(good, "tpu_pbrt/serve/service.py") == []

    def test_vtime_written_outside_policy_api(self):
        assert _rules(
            "def cheat(ts):\n    ts.vtime = 0.0\n", "tpu_pbrt/serve/queue.py"
        ) == ["SV-VTIME"]
        assert _rules(
            "def cheat(ts):\n    ts.vtime += 1.0\n",
            "tpu_pbrt/serve/service.py",
        ) == ["SV-VTIME"]

    def test_pragma_suppression(self):
        src = (
            "import time\n\ndef f(self):\n"
            "    return time.monotonic()  # jaxlint: disable=SV-CLOCK\n"
        )
        assert _rules(src, "tpu_pbrt/serve/service.py") == []

    def test_syntax_error_is_a_finding_not_a_crash(self):
        assert _rules("def broken(:\n", "tpu_pbrt/serve/service.py") == [
            "SV-PARSE"
        ]

    def test_repo_tree_is_clean(self):
        assert pc.sv_lint_tree() == []


# ---------------------------------------------------------------------------
# mutation-regression corpus
# ---------------------------------------------------------------------------


class TestMutationCorpus:
    @pytest.mark.parametrize(
        "case", pc.MUTATION_CASES, ids=lambda c: c.name
    )
    def test_mutant_detected_by_name_via_cli(self, case, explore, capsys):
        """The REAL entry point: `tools/explore.py --mutate NAME` must
        exit non-zero and print the expected invariant."""
        rc = explore.main(["--mutate", case.name])
        out = capsys.readouterr().out
        assert rc == 1, out
        assert f"PROTOCHECK VIOLATION {case.expect}" in out
        assert case.historical in out

    @pytest.mark.parametrize(
        "case", pc.MUTATION_CASES, ids=lambda c: c.name
    )
    def test_clean_tree_passes_the_same_decisions(self, case):
        viol, log = pc.run_mutation_case(case.name, mutate=False)
        assert viol == []
        # and byte-identically so: the determinism contract
        viol2, log2 = pc.run_mutation_case(case.name, mutate=False)
        assert viol2 == [] and log2 == log

    def test_unknown_mutation_name_rejected(self):
        with pytest.raises(KeyError):
            pc.mutation_case("not-a-mutation")

    def test_corpus_covers_the_seeded_bugs(self):
        assert {c.expect for c in pc.MUTATION_CASES} == {
            "PROTO-WEDGE", "PROTO-VTIME", "PROTO-DEFER", "PROTO-HBM",
            "PROTO-ROUTE-DUP",
        }


# ---------------------------------------------------------------------------
# bounded explorer
# ---------------------------------------------------------------------------


class TestExplorer:
    def test_clean_grid_smoke(self, explore):
        # small budget: the full CI budget runs in tools/ci.sh; here we
        # only need every scenario to boot, explore, and stay clean
        assert explore.run_ci(seed=0, max_nodes=10, max_depth=4) == []

    def test_pruning_happens(self, explore):
        duo = next(s for s in pc.smoke_scenarios() if s.name == "duo-d2")
        ex = explore.Explorer(duo, seed=0, max_nodes=40, max_depth=7).run()
        assert ex.violations == []
        assert ex.pruned > 0  # commuting interleavings collapse

    def test_canonical_drain_replays_byte_identically(self, explore):
        duo = next(s for s in pc.smoke_scenarios() if s.name == "duo-d1")
        decisions, log1, viol = explore.canonical_drain(duo, seed=0)
        assert viol == []
        assert explore.replay_log(duo, decisions, seed=0) == log1

    def test_fault_scenario_drains_clean(self, explore):
        # a dispatch:fail placement must recover through the real
        # backoff ladder and still reconcile counters + film bits
        sc = next(
            s for s in pc.smoke_scenarios() if "dispatch:fail" in s.fault
        )
        _, _, viol = explore.canonical_drain(sc, seed=0)
        assert viol == []

    def test_trace_export_accepted_by_scope(self, explore, tmp_path):
        duo = next(s for s in pc.smoke_scenarios() if s.name == "duo-d2")
        out = explore.export_trace(duo, str(tmp_path / "trace.json"), seed=0)
        doc = json.loads(open(out).read())
        assert doc["otherData"]["clock"] == "virtual"
        scope = _load_tool("scope")
        assert scope.main([out, "--check"]) == 0
