"""Test configuration: force an 8-device CPU mesh before any test imports.

This mirrors how the reference's distributed layer is tested without a
cluster (SURVEY.md §4): a virtual 8-device CPU platform exercises the
shard_map/psum code paths that run over ICI on real TPU hardware.

The platform is forced through jax.config as well as the environment so
that a machine with an accelerator still runs the suite on the CPU: unit
tests must be hardware-independent and deterministic. Set
TPU_PBRT_TEST_PLATFORM=tpu to run the suite on real hardware instead
(one process owns the chip; see README "Running on the chip").
"""

import os

_platform = os.environ.get("TPU_PBRT_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
# The suite's wall time is tracing and XLA:CPU compilation of whole render
# programs; the renders themselves are tiny. Level 0 compiles the same
# programs ~35x faster than the optimizing pipeline (measured in round 4:
# the mesh-SPPM module 728 s -> 21 s). Set TPU_PBRT_TEST_XLA_OPT=default
# to run the optimized pipeline instead (e.g. when timing kernels on real
# hardware).
if (
    _platform == "cpu"
    and os.environ.get("TPU_PBRT_TEST_XLA_OPT", "0") == "0"
    and "xla_backend_optimization_level" not in _flags
):
    _flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = _flags
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", _platform)

# Persistent XLA compilation cache, placed like every entry point's:
# JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache. It is not
# in git, and a PR that changes the chunk program empties it in effect.
# COLD, the driver's command (six workers, whole FILES to a worker:
# `--dist loadfile`, cut at 1470 s) took 667 s here, the costliest file
# 197 s; warm 657 s: the cache saves XLA's share, not the tracing, and six
# workers on eight cores wait for each other (PR 28; the parent: 1943 s
# cold, 877 s warm). The table by file, and the rule that no file may pass
# 300 s cold, are in tests/test_suite_budget.py.
from tpu_pbrt.config import place_compile_cache  # noqa: E402

place_compile_cache()

import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402

import pytest  # noqa: E402

# Set-up, call and teardown of every case each run under this limit (the
# shared renders happen in fixtures). No tier-1 case comes near it cold
# (tests/test_suite_budget.py): one that gets here waits on something that
# will not come. A `slow` case that builds a program of minutes says so
# beside itself: @pytest.mark.case_limit(seconds).
CASE_LIMIT_S = 300.0

# Where the backstop writes. While a case runs, fd 2 and sys.stderr are the
# capture's temporary file, which is lost with a worker that exits.
_backstop_fd = 2


def pytest_configure(config):
    # capture is suspended while pytest configures: this is the stderr the
    # process was started with (in an xdist worker, the controller's)
    global _backstop_fd
    _backstop_fd = os.dup(2)


@contextlib.contextmanager
def case_limit(seconds):
    """Fail the enclosed code with the Python stack of every thread once
    `seconds` of wall time have passed. SIGALRM reaches only the main
    thread, and only when the interpreter has control: a hang inside
    native code needs the backstop in _phase_limit. Leaves no timer armed
    and the handler it found; does nothing where the platform has no
    SIGALRM."""
    if not hasattr(signal, "SIGALRM"):
        yield
        return

    def on_alarm(signum, frame):
        with tempfile.TemporaryFile("w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        pytest.fail(f"case passed its limit of {seconds:g} s\n{stacks}", pytrace=False)

    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)


@contextlib.contextmanager
def _phase_limit(item):
    """The limit around ONE phase of a case, where pytest turns what the
    alarm raises into a failed case; around the whole protocol it would
    fire between phases, inside pytest's or xdist's own code."""
    own = item.get_closest_marker("case_limit")
    limit = float(own.args[0]) if own else CASE_LIMIT_S
    # the backstop for a hang in native code, where no Python handler
    # runs: it dumps the stacks and ends the worker (xdist starts another)
    faulthandler.dump_traceback_later(2 * limit, exit=True, file=_backstop_fd)
    try:
        with case_limit(limit):
            yield
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_setup(item):
    with _phase_limit(item):
        return (yield)


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_call(item):
    with _phase_limit(item):
        return (yield)


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_teardown(item):
    with _phase_limit(item):
        return (yield)


@pytest.fixture(autouse=True)
def _sync_tpu_pbrt_config():
    """TPU_PBRT_* knobs are snapshotted at import by tpu_pbrt.config;
    tests that mutate os.environ mid-test call config.reload() at the
    mutation point. This autouse resync at both test boundaries keeps a
    test's leftover env mutations (e.g. monkeypatch teardown, which
    restores os.environ but knows nothing of the snapshot) from
    poisoning the knobs later tests see."""
    from tpu_pbrt import config

    config.reload()
    yield
    config.reload()
