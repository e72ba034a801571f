"""hbmcheck (ISSUE 18): static HBM residency, liveness & capacity
verification across the serve stack (analysis layer 6).

Five pieces under test: the memory model itself (film/job/worst-case
closed forms vs the HC-ALIAS symbolic buffer graph), the HC-* rule
families with synthetic positives AND negatives, the committed
hbm_budgets.json gate (regression -> --update-budgets -> clean round
trip), the --derive-hbm-caps inversion (the committed serve knob
defaults must be reproducible consequences of the model), and the
dynamic cross-check — the serve leak fixes this PR landed, asserted on
a REAL RenderService under a VirtualClock, plus the seeded
park-skips-film-release mutant flagged by PROTO-HBM through the real
`tools/explore.py --mutate` entry point.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from tpu_pbrt.analysis import hbmcheck as hc
from tpu_pbrt.analysis import protocheck as pc
from tpu_pbrt.integrators.common import live_film_carries

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"_hbmcheck_test_{name}", os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def explore():
    return _load_tool("explore")


# ---------------------------------------------------------------------------
# the memory model
# ---------------------------------------------------------------------------


class TestModel:
    def test_film_state_bytes_matches_live_layout(self):
        # rgb(3) + weight(1) + splat(3) f32 planes = 28 B/pixel
        assert hc.film_state_bytes(1, 1) == 28
        assert hc.film_state_bytes(512, 512) == 512 * 512 * 28
        assert hc.film_state_bytes(2, 2) == 112  # the protocheck stub film

    def test_live_film_carries_donation_collapse(self):
        # depth 1 donates in/out: ONE buffer; depth d>1 pins every
        # un-donated in-flight input carry + the newest output
        assert live_film_carries(1) == 1
        assert live_film_carries(0) == 1  # clamped
        assert live_film_carries(2) == 3
        assert live_film_carries(3) == 4

    def test_job_bytes_closed_form(self):
        fb = hc.film_state_bytes(*hc.REF_FILM)
        assert hc.job_hbm_bytes(fb, 1) == fb + hc.COUNTER_BYTES_PER_SLICE
        assert hc.job_hbm_bytes(fb, 2) == 3 * fb + 2 * hc.COUNTER_BYTES_PER_SLICE

    def test_serve_model_totals_add_up(self):
        m = hc.serve_model()
        assert m["total_bytes"] == (
            m["resident_bytes"] + m["jobs_bytes"]
            + m["prefetch_bytes"] + m["staging_bytes"]
        )
        assert m["jobs_bytes"] == m["max_active"] * m["job_bytes"]
        # the configured default budget is finite (the PR-18 knob)
        assert m["resident_bytes"] > 0


class TestHcCap:
    def test_clean_model_fits(self):
        assert hc.check_capacity(hc.serve_model()) == []

    def test_synthetic_over_cap_named(self):
        # a resident budget past the smallest platform's HBM must fail
        # naming the rule (the ISSUE-18 acceptance shape)
        m = hc.serve_model(resident_bytes=64 * hc.GiB)
        errs = hc.check_capacity(m)
        assert len(errs) == 1 and errs[0].startswith("HC-CAP:")

    def test_over_cap_config_exits_nonzero_via_cli(self):
        # the REAL entry point: the synthetic over-cap config must exit
        # non-zero and name HC-CAP
        import subprocess
        import sys

        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            TPU_PBRT_SERVE_RESIDENT_MB="65536",
        )
        r = subprocess.run(
            [sys.executable, "-m", "tpu_pbrt.analysis.hbmcheck"],
            capture_output=True, text=True, env=env, cwd=REPO,
        )
        assert r.returncode == 1, r.stdout + r.stderr
        assert "HC-CAP" in r.stdout


class TestHcAcct:
    def test_reference_scene_within_tolerance(self):
        assert hc.acct_check() == []

    def test_lying_nbytes_detected(self):
        # an estimator trusting a bogus nbytes attribute must be caught
        # against the aval-exact shape x itemsize walk
        class _Lying:
            shape = (1024, 1024)
            dtype = np.float32
            nbytes = 64  # lies: exact is 4 MiB

        sc = hc.reference_scene()
        sc.dev["liar"] = _Lying()
        errs = hc.acct_check(sc)
        assert len(errs) == 1 and errs[0].startswith("HC-ACCT:")

    def test_exact_walk_is_shape_times_itemsize(self):
        sc = hc.reference_scene()
        want = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for a in (
                sc.dev["tri_verts9T"], sc.dev["tstream"]["slabs48"],
                sc.dev["tstream"]["child_idx"], sc.dev["tex_atlas_u8"],
                sc.dev["light_cdf"], sc.dev["mat_table"],
            )
        ) + hc.film_state_bytes(*hc.REF_FILM)
        assert hc.exact_scene_bytes(sc) == want


class TestHcAlias:
    def test_clean_graphs_reproduce_closed_form(self):
        assert hc.alias_audit() == []

    def test_depth1_donation_is_one_buffer(self):
        fb = hc.film_state_bytes(*hc.REF_FILM)
        bufs = hc.job_buffers(fb, 1)
        # carry_out and ckpt_snap both alias carry0: dedup counts once
        assert hc.dedup_bytes(bufs) == fb + hc.COUNTER_BYTES_PER_SLICE

    def test_donated_without_alias_edge_flagged(self):
        bufs = [
            hc.Buf("carry0", 100),
            hc.Buf("carry_out", 100, donated=True),  # missing alias_of
        ]
        errs = hc.check_alias(bufs)
        assert len(errs) == 1 and "double-count" in errs[0]
        assert errs[0].startswith("HC-ALIAS:")

    def test_unresolvable_alias_flagged(self):
        errs = hc.check_alias(
            [hc.Buf("snap", 100, alias_of="ghost")]
        )
        assert len(errs) == 1 and "unknown buffer" in errs[0]


# ---------------------------------------------------------------------------
# HC-LEAK static rule
# ---------------------------------------------------------------------------

_SVC = "tpu_pbrt/serve/service.py"
_RES = "tpu_pbrt/serve/residency.py"


def _rules(src, rel):
    return [v.rule for v in hc.hc_leak_source(src, rel)]


class TestHcLeak:
    def test_terminal_without_release_flagged(self):
        src = (
            "def fail(self, job):\n"
            "    job.status = FAILED\n"
            "    self.residency.unpin(job.resident_key)\n"
        )
        vs = hc.hc_leak_source(src, _SVC)
        assert [v.rule for v in vs] == ["HC-LEAK"]
        assert "releases no device buffers" in vs[0].message

    def test_terminal_with_release_helper_clean(self):
        src = (
            "def fail(self, job):\n"
            "    job.status = FAILED\n"
            "    self._release_device(job)\n"
            "    self.residency.unpin(job.resident_key)\n"
        )
        assert _rules(src, _SVC) == []

    def test_inline_release_requires_all_four_counter_lists(self):
        head = (
            "def fail(self, job):\n"
            "    job.status = CANCELLED\n"
            "    job.state = None\n"
            "    self.residency.unpin(job.resident_key)\n"
        )
        partial = head + (
            "    job.ray_counts.clear()\n"
            "    job.occ_counts.clear()\n"
        )
        full = partial + (
            "    job.ctr_counts.clear()\n"
            "    job.nf_counts.clear()\n"
        )
        assert _rules(partial, _SVC) == ["HC-LEAK"]
        assert _rules(full, _SVC) == []

    def test_terminal_without_unpin_flagged(self):
        src = (
            "def fin(self, job):\n"
            "    job.status = DONE\n"
            "    self._release_device(job)\n"
        )
        vs = hc.hc_leak_source(src, _SVC)
        assert [v.rule for v in vs] == ["HC-LEAK"]
        assert "pin" in vs[0].message

    def test_non_terminal_status_untouched(self):
        src = "def park(self, job):\n    job.status = PARKED\n"
        assert _rules(src, _SVC) == []

    def test_outside_serve_modules_unscoped(self):
        src = "def fail(self, job):\n    job.status = FAILED\n"
        assert _rules(src, "tpu_pbrt/film/image.py") == []

    def test_eviction_without_pin_check_flagged(self):
        bad = (
            "def evict(self):\n"
            "    for k in list(self._entries):\n"
            "        del self._entries[k]\n"
        )
        good = (
            "def evict(self):\n"
            "    for k, e in list(self._entries.items()):\n"
            "        if e.pins == 0:\n"
            "            del self._entries[k]\n"
        )
        vs = hc.hc_leak_source(bad, _RES)
        assert [v.rule for v in vs] == ["HC-LEAK"]
        assert "pin counts" in vs[0].message
        assert _rules(good, _RES) == []

    def test_pragma_suppression(self):
        src = (
            "def fail(self, job):  # jaxlint: disable=HC-LEAK\n"
            "    job.status = FAILED\n"
        )
        assert _rules(src, _SVC) == []

    def test_syntax_error_is_a_finding_not_a_crash(self):
        assert _rules("def broken(:\n", _SVC) == ["HC-PARSE"]

    def test_repo_tree_is_clean(self):
        assert hc.hc_leak_tree() == []


# ---------------------------------------------------------------------------
# budgets: regression -> refresh -> clean round trip
# ---------------------------------------------------------------------------


class TestBudgets:
    def test_committed_budgets_gate_clean(self):
        entries = hc.collect_entries()
        errs, _warns = hc.check_budgets(entries, hc.load_budgets())
        assert errs == []

    def test_missing_entry_is_an_error(self):
        errs, _ = hc.check_budgets(hc.collect_entries(), {"entries": {}})
        assert errs and all("no committed HBM budget" in e for e in errs)

    def test_regression_then_update_then_clean(self, tmp_path):
        p = tmp_path / "hbm_budgets.json"
        entries = hc.collect_entries()
        hc.save_budgets(entries, p, tolerance=0.1)
        # a 2x footprint regression must gate...
        grown = {
            k: dict(v, hbm_bytes=v["hbm_bytes"] * 2)
            for k, v in entries.items()
        }
        errs, _ = hc.check_budgets(grown, hc.load_budgets(p))
        assert errs and all("regressed" in e for e in errs)
        # ...an improvement only warns (ratchet hint)...
        shrunk = {
            k: dict(v, hbm_bytes=max(v["hbm_bytes"] // 2, 1))
            for k, v in entries.items()
        }
        errs, warns = hc.check_budgets(shrunk, hc.load_budgets(p))
        assert errs == [] and warns
        # ...and --update-budgets closes the loop, keeping tolerance
        hc.save_budgets(grown, p, tolerance=0.1)
        errs, warns = hc.check_budgets(grown, hc.load_budgets(p))
        assert errs == [] and warns == []
        assert json.loads(p.read_text())["tolerance"] == 0.1

    def test_stale_entry_warns(self, tmp_path):
        p = tmp_path / "hbm_budgets.json"
        entries = dict(hc.collect_entries())
        entries["serve.ghost"] = {"hbm_bytes": 1, "fingerprint": "x"}
        hc.save_budgets(entries, p)
        del entries["serve.ghost"]
        errs, warns = hc.check_budgets(entries, hc.load_budgets(p))
        assert errs == []
        assert any("serve.ghost" in w and "no live model term" in w
                   for w in warns)

    def test_run_hbmcheck_repo_gate_clean(self):
        errors, _warnings = hc.run_hbmcheck()
        assert errors == []


# ---------------------------------------------------------------------------
# --derive-hbm-caps: knob defaults are consequences of the model
# ---------------------------------------------------------------------------


class TestDeriveCaps:
    def test_derived_caps_admit_the_committed_defaults(self):
        from tpu_pbrt.config import cfg

        d = hc.derive_hbm_caps()
        assert hc.check_hbm_caps(d) == []
        c = d["configured"]
        assert c["serve_resident_mb"] == cfg.serve_resident_mb == 12288.0
        assert c["pipeline_depth"] == cfg.pipeline == 2
        worst = min(
            p["max_resident_mb_aligned"] for p in d["platforms"].values()
        )
        # the committed default IS the derive output's floor: the
        # largest 1024-aligned resident budget safe on every platform,
        # within one alignment quantum (the operator margin)
        assert worst - 1024 <= cfg.serve_resident_mb <= worst
        assert all(
            p["max_pipeline_depth"] >= cfg.pipeline
            for p in d["platforms"].values()
        )

    def test_caps_scale_with_hbm(self):
        d = hc.derive_hbm_caps()
        plats = d["platforms"]
        assert plats["v5e"]["max_active"] < plats["v4"]["max_active"]
        assert plats["v4"]["max_active"] < plats["v5p"]["max_active"]

    def test_overcommitted_knobs_flagged_by_name(self):
        d = hc.derive_hbm_caps()
        d["configured"]["serve_resident_mb"] = 1e9  # absurd
        d["configured"]["pipeline_depth"] = 10_000
        errs = hc.check_hbm_caps(d)
        assert len(errs) == 2
        assert all(e.startswith("HC-CAP:") for e in errs)

    def test_cli_reproduces_defaults(self):
        import subprocess
        import sys

        r = subprocess.run(
            [sys.executable, "-m", "tpu_pbrt.analysis.hbmcheck",
             "--derive-hbm-caps", "--format", "json"],
            capture_output=True, text=True, cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        assert doc["configured"]["serve_resident_mb"] == 12288.0
        assert doc["configured"]["pipeline_depth"] == 2


# ---------------------------------------------------------------------------
# bench fields (satellite: the static HBM half of the bench line)
# ---------------------------------------------------------------------------


class TestBenchFields:
    def test_fields_present_and_sane(self):
        f = hc.bench_fields(512, 512)
        assert set(f) == {"static_hbm_per_job", "hbm_headroom"}
        assert f["static_hbm_per_job"] == hc.serve_model()["job_bytes"]
        assert 0.0 < f["hbm_headroom"] < 1.0

    def test_bench_whitelist_forwards_the_fields(self):
        # bench.py's subprocess whitelist must pass both keys through
        # (measured AND outage JSON lines ride the same helper)
        import bench

        src = open(os.path.join(REPO, "bench.py")).read()
        assert '"static_hbm_per_job"' in src
        assert '"hbm_headroom"' in src
        assert hasattr(bench, "static_wave_cost")


# ---------------------------------------------------------------------------
# the serve leak fixes (satellite 1) — real service, virtual clock
# ---------------------------------------------------------------------------


def _stub_service():
    """A real RenderService under a VirtualClock with protocheck's stub
    harness (2x2 film, 64 rays/chunk, no compile)."""
    model = pc.ProtocolModel(
        pc.Scenario(
            name="leakfix",
            jobs=(pc.JobSpec("j", n_chunks=4, checkpoint_every=2, depth=2),),
            allow=("submit", "step", "preempt", "cancel"),
        ),
        seed=0,
    )
    return model


def _device_refs(job):
    return (
        job.state, job.window,
        job.ray_counts, job.occ_counts, job.ctr_counts, job.nf_counts,
    )


class TestLeakFixes:
    def test_cancel_mid_render_releases_everything(self):
        m = _stub_service()
        try:
            m.apply(("submit", 0))
            m.apply(("step",))
            m.apply(("step",))
            job = m.svc.jobs["j"]
            assert job.ray_counts  # device counters accumulated
            m.svc.cancel("j")
            assert job.state is None and job.window is None
            assert job.plan is None  # jit closures no longer pin scene HBM
            assert not any(
                (job.ray_counts, job.occ_counts,
                 job.ctr_counts, job.nf_counts)
            )
            assert all(
                n == 0 for n in m.svc.residency.pin_counts().values()
            )
            assert m.violations == []
        finally:
            m.close()

    def test_finalize_clears_counters_and_plan_keeps_result(self):
        m = _stub_service()
        try:
            m.apply(("submit", 0))
            for _ in range(8):
                if m.svc.jobs["j"].status == "done":
                    break
                m.apply(("step",))
            job = m.svc.jobs["j"]
            assert job.status == "done"
            assert job.plan is None and job.state is None
            assert not job.ray_counts and job.window is None
            # intentional retention: the result film survives
            assert job.result is not None and job.result.film_state is not None
            # poll/progress still report totals without the plan
            assert m.svc.poll("j")["chunks_total"] == 4
            assert job.progress() == 1.0
            assert m.violations == []
        finally:
            m.close()

    def test_park_releases_film_carry(self):
        m = _stub_service()
        try:
            m.apply(("submit", 0))
            m.apply(("step",))
            m.apply(("preempt", "j"))
            job = m.svc.jobs["j"]
            assert job.status == "paused"
            assert job.state is None and job.window is None
            assert not job.ray_counts
            assert m.violations == []
        finally:
            m.close()

    def test_prefetched_then_cancelled_releases_activation(self):
        # the second ISSUE-18 suspect: a job activated by the prefetch
        # lookahead, then cancelled before its first dispatch, must not
        # strand the prefetched film state
        m = pc.ProtocolModel(
            pc.Scenario(
                name="leakfix-prefetch",
                jobs=(
                    pc.JobSpec("a", n_chunks=3, depth=2),
                    pc.JobSpec("b", n_chunks=3, depth=2),
                ),
                allow=("submit", "step", "cancel"),
            ),
            seed=0,
        )
        try:
            m.apply(("submit", 0))
            m.apply(("submit", 1))
            m.apply(("step",))  # dispatches one, prefetch-activates other
            pre = [
                j for j in m.svc.jobs.values()
                if j.status != "active" and j.state is not None
            ]
            for j in list(m.svc.jobs.values()):
                m.svc.cancel(j.job_id)
                assert j.state is None and j.window is None
                assert not j.ray_counts and j.plan is None
            held, _total = m._modeled_hbm()
            assert held == 0  # the PROTO-HBM drain baseline
            assert m.violations == []
            del pre
        finally:
            m.close()

    def test_retry_exhaustion_releases_on_failed(self):
        m = pc.ProtocolModel(
            pc.Scenario(
                name="leakfix-fail",
                jobs=(pc.JobSpec("j", n_chunks=2, depth=1),),
                fault="dispatch:fail@chunk=0&times=99",
                allow=("submit", "step", "advance"),
            ),
            seed=0,
        )
        try:
            m.apply(("submit", 0))
            for _ in range(64):
                job = m.svc.jobs["j"]
                if job.status == "failed":
                    break
                if m.apply(("step",)) == "idle":
                    m.apply(("advance",))
            job = m.svc.jobs["j"]
            assert job.status == "failed"
            assert job.state is None and job.window is None
            assert not any(
                (job.ray_counts, job.occ_counts,
                 job.ctr_counts, job.nf_counts)
            )
            assert job.plan is None
            held, _ = m._modeled_hbm()
            assert held == 0
        finally:
            m.close()


# ---------------------------------------------------------------------------
# the dynamic cross-check: PROTO-HBM + the seeded mutant via the CLI
# ---------------------------------------------------------------------------


class TestProtoHbm:
    def test_leak_mutant_detected_by_name_via_cli(self, explore, capsys):
        rc = explore.main(["--mutate", "park-skips-film-release"])
        out = capsys.readouterr().out
        assert rc == 1, out
        assert "PROTOCHECK VIOLATION PROTO-HBM" in out
        assert "film carry" in out

    def test_clean_tree_passes_the_leak_case(self):
        viol, _log = pc.run_mutation_case(
            "park-skips-film-release", mutate=False
        )
        assert viol == []

    def test_watermark_bounded_and_returns_to_baseline(self, explore):
        duo = next(s for s in pc.smoke_scenarios() if s.name == "duo-d2")
        _decisions, _log, viol = explore.canonical_drain(duo, seed=0)
        assert viol == []

    def test_static_worst_bounds_modeled_peak(self):
        m = _stub_service()
        try:
            m.apply(("submit", 0))
            m.apply(("step",))
            m.apply(("step",))
            assert 0 < m.hbm_peak <= m._static_worst_hbm()
        finally:
            m.close()
