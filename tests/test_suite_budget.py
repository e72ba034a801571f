"""The tier-1 suite's clock (ISSUE 28): no file that one worker cannot
finish, a limit on every case.

The driver's command hands whole FILES to its six workers (`--dist
loadfile`) and stops at 1470 s, so the largest file bounds the run. The
table is each file's cold cost: the sum of its cases' durations in ONE run
of the driver's command with an empty JAX_COMPILATION_CACHE_DIR (PR 28, 8
cores, six workers, 667 s of wall time: seconds of a loaded machine, to be
read against each other; another run of one tree read up to a third
more). A PR that changes the chunk program makes the next run a cold one.
The rows of test_accel.py, test_chaos.py and test_stream_oracle.py are
from PR 29's cold run (763 s of wall time, the files' sum 3487 s), the
row of test_distributed.py from PR 30's (869 s, sum 4475 s: a slower
machine that day; the file gained the corner-scene mesh renders). PR 31
(builder, 8 cores): test_crown_geometry_config.py alone with its programs
not yet built 70 s in its one render and reference, 90 under the suite's
load; test_stream_oracle.py gained twelve pair-sort cases, 94 -> 135 by its
cases' count (107 s in the driver's command with most programs cached).
PR 32: sixteen cases of the lower block heights and three of the cut, 40 ->
59 cases, 135 -> 199 by their count (144 s in the driver's command with
every stream-traced program new to the cache: 500 s of wall time, sum 2235 s,
733 passed). PR 33: test_halton_reference.py, three renders of a 32x32 scene
at 8 spp (pool, fixed-batch loop, the mutated pool) and the generator's
cases, 67 s alone with an empty cache, 127 by its cases' count in the
driver's command (six workers, every halton program new to the cache: 759 s
of wall time, sum 3712 s, 745 passed); test_distributed.py gained the halton
mesh case (two programs, 20 s alone and cold). The files whose scenes name
halton or no sampler now build pool programs: test_render.py 164 -> 222,
test_render_lights.py 172 -> 214 in that run. PR 35: test_setup_trace.py, one
16x16 cornell render and the set-up of killeroo-class's `test` preset (chunk
and audit programs), 22 s alone with its programs cached, 40 taken for a cold
run under the suite's load. PR 36: test_tpu_layout.py, ONE compile of the
stream tracer for a described v5e chip at crown-geometry's shapes (no cache:
a described device's programs cannot be read back), 48 s alone, 75 taken
under the suite's load; test_stream_oracle.py gained 41 cases (the pack
against numpy, the `fan` and `across` scenes), +25 s by their count. PR 37:
test_manylight_reference.py, one render of killeroo-manylight's `test` preset
(256 light rows), its reference and bfloat16 control and two lowerings of a
264-row variant, 70 s alone with an empty cache, 100 taken under the suite's
load; test_lightdistrib.py 2 -> 27 cases (the table at five sizes, the two
scene cases on each side of the dense select, the packed row, the fallback),
85 -> 175 s alone and cold, 230 taken.
"""

import glob
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

import conftest

FILE_LIMIT_S = 300

COLD_SECONDS = {
    "test_accel.py": 109,
    "test_bdpt.py": 96,
    "test_bdpt_lights.py": 170,
    "test_bssrdf.py": 5,
    "test_bxdf_rough.py": 14,
    "test_chaos.py": 138,
    "test_checkpoint_stats.py": 70,
    "test_cornell_config.py": 60,
    "test_cost.py": 34,
    "test_crown_geometry_config.py": 90,
    "test_disney.py": 67,
    "test_distributed.py": 239,
    "test_film_imageio.py": 9,
    "test_fleet.py": 1,
    "test_fourier.py": 21,
    "test_hair.py": 39,
    "test_halton_reference.py": 127,
    "test_hbmcheck.py": 7,
    "test_interpolation.py": 19,
    "test_jaxlint.py": 4,
    "test_jaxpr_audit.py": 114,
    "test_lightdistrib.py": 230,
    "test_load.py": 2,
    "test_manylight_reference.py": 100,
    "test_media.py": 116,
    "test_media_furnace.py": 171,
    "test_media_null.py": 197,
    "test_metrics.py": 17,
    "test_mix.py": 61,
    "test_mlt.py": 122,
    "test_motion.py": 36,
    "test_native.py": 12,
    "test_obs.py": 83,
    "test_parser.py": 1,
    "test_phases.py": 83,
    "test_pipeline.py": 138,
    "test_protocheck.py": 3,
    "test_raydiff.py": 28,
    "test_realistic.py": 24,
    "test_render.py": 222,
    "test_render_lights.py": 214,
    "test_render_small.py": 65,
    "test_samplers.py": 77,
    "test_sampling.py": 12,
    "test_scope.py": 27,
    "test_serve.py": 97,
    "test_setup_trace.py": 40,
    "test_shardcheck.py": 25,
    "test_sobol.py": 41,
    "test_sppm.py": 122,
    "test_stream_oracle.py": 224,
    "test_suite_budget.py": 5,
    "test_textures.py": 41,
    "test_tpu_layout.py": 75,
    "test_wavefront.py": 138,
}


def test_every_test_file_is_in_the_table_and_under_the_file_limit():
    here = os.path.dirname(os.path.abspath(__file__))
    files = sorted(os.path.basename(p) for p in glob.glob(os.path.join(here, "test_*.py")))
    unmeasured = [f for f in files if f not in COLD_SECONDS]
    too_long = {f: s for f, s in COLD_SECONDS.items() if s > FILE_LIMIT_S}
    gone = sorted(set(COLD_SECONDS) - set(files))
    assert not unmeasured and not too_long, (
        f"measure it cold and add it here: {unmeasured}; over {FILE_LIMIT_S} s, "
        f"split it by cost: {too_long}"
    )
    assert not gone, f"in the table, not in tests/: {gone}"


@pytest.mark.skipif(not hasattr(signal, "SIGALRM"), reason="the limit is a SIGALRM timer")
class TestCaseLimit:
    def test_this_case_runs_under_it(self):
        """Under xdist too: signals reach only a worker's main thread."""
        assert threading.current_thread() is threading.main_thread()
        left, _ = signal.getitimer(signal.ITIMER_REAL)
        assert 0 < left <= conftest.CASE_LIMIT_S

    @pytest.mark.case_limit(2 * conftest.CASE_LIMIT_S)
    def test_a_case_can_name_a_limit_of_its_own(self):
        left, _ = signal.getitimer(signal.ITIMER_REAL)
        assert conftest.CASE_LIMIT_S < left <= 2 * conftest.CASE_LIMIT_S

    # the two below take the suite's timer for their own: what is left of
    # them runs under the backstop alone

    def test_it_fires_with_the_stacks_of_every_thread(self):
        t0 = time.monotonic()
        with pytest.raises(pytest.fail.Exception) as caught:
            with conftest.case_limit(0.2):
                time.sleep(2)
        assert time.monotonic() - t0 < 1.5
        said = str(caught.value)
        assert "limit of 0.2 s" in said
        assert "most recent call first" in said
        assert "test_it_fires_with_the_stacks_of_every_thread" in said
        assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)

    def test_a_case_under_its_limit_leaves_no_timer_armed(self):
        before = signal.getsignal(signal.SIGALRM)
        with conftest.case_limit(5.0):
            assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 5.0
        assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
        assert signal.getsignal(signal.SIGALRM) is before

    def test_the_backstop_writes_past_the_capture(self, request):
        """While a case runs fd 2 is the capture's temporary file, and what
        the backstop wrote there would go with the worker it ends."""
        if request.config.getoption("capture") != "fd":
            pytest.skip("fd 2 is not captured in this run")
        assert not os.path.samestat(os.fstat(conftest._backstop_fd), os.fstat(2))

    def test_a_case_deaf_to_the_alarm_ends_with_its_stacks(self, tmp_path):
        """A hang in native code never lets the SIGALRM handler run. A case
        that blocks the signal stands in for one: the backstop, at twice
        the limit, puts every thread's stack on the real stderr and ends the
        process (under xdist that is one worker, and the run goes on)."""
        case = tmp_path / "test_deaf.py"
        case.write_text(
            "import signal, time\n"
            "import pytest\n"
            "@pytest.mark.case_limit(0.5)\n"
            "def test_deaf_to_the_alarm():\n"
            "    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})\n"
            "    time.sleep(60)\n"
        )
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        t0 = time.monotonic()
        # capture on, as in the driver's run; `-p conftest` because the case
        # lies outside tests/
        ran = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             "-c", os.path.join(repo, "pyproject.toml"), "--rootdir", repo,
             "-p", "conftest", str(case)],
            cwd=repo, capture_output=True, text=True, timeout=50,
            env={**os.environ, "PYTHONPATH": os.path.join(repo, "tests") + os.pathsep + repo},
        )
        assert time.monotonic() - t0 < 50
        assert ran.returncode != 0
        assert "Timeout (0:00:01)!" in ran.stderr, ran.stderr[-2000:]
        assert "in test_deaf_to_the_alarm" in ran.stderr
