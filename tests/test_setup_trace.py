"""Set-up under spans and compile-stage counters (ISSUE 35): what `COMPILES`
keeps of jax's trace, lower, backend and cache events, which span carries
what fell inside it, a span placed on the host's clock, and the tiling: a
set-up whose wall time lies under the program's spans.
"""

import os
import sys
import time

import pytest

from tpu_pbrt.obs import compiles
from tpu_pbrt.obs.compiles import COMPILES, CompileTracker
from tpu_pbrt.obs.trace import TRACE, TraceRecorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_EV = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EV = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EV = "/jax/core/compile/backend_compile_duration"
RETRIEVAL_EV = "/jax/compilation_cache/cache_retrieval_time_sec"


def _program(t, name, trace=0.0, lower=0.0, backend=0.0, inner=()):
    """Feed `t` the events jax records for one program: the trace's start
    and end (with `inner` jitted functions traced inside it), the lowering,
    the backend compile; jax names the later stages by the module."""
    t._on_scalar(TRACE_EV, 0.0, fun_name=name)
    for inner_name, seconds in inner:
        t._on_scalar(TRACE_EV, 0.0, fun_name=inner_name)
        t._on_duration(TRACE_EV, seconds, fun_name=inner_name)
    t._on_duration(TRACE_EV, trace, fun_name=name)
    t._on_scalar(LOWER_EV, 0.0, fun_name=f"jit({name})")
    t._on_duration(LOWER_EV, lower, fun_name=f"jit({name})")
    t._on_scalar(BACKEND_EV, 0.0, fun_name=f"jit({name})")
    t._on_duration(BACKEND_EV, backend, fun_name=f"jit({name})")


class TestListener:
    def test_totals_and_rows_by_program(self):
        t = CompileTracker()
        _program(t, "chunk_fn", 2.0, 0.5, 30.0, inner=[("take", 0.25), ("take", 0.25)])
        t._on_event("/jax/compilation_cache/cache_misses")
        _program(t, "audit_rays", 0.125, 0.0625, 0.5)
        t._on_duration(RETRIEVAL_EV, 0.25)
        t._on_event("/jax/compilation_cache/cache_hits")
        t._on_event("/jax/compilation_cache/tasks_using_cache")  # not ours
        t._on_duration("/jax/compilation_cache/compile_time_saved_sec", 9.0)
        # every trace is counted, the outermost of a thread alone is timed
        assert (t.traces, t.programs) == (4, 2)
        assert (t.trace_seconds, t.lower_seconds, t.seconds) == (2.125, 0.5625, 30.5)
        assert (t.retrieval_seconds, t.cache_hits, t.cache_misses) == (0.25, 1, 1)
        rows = t.by_program()
        assert [r["program"] for r in rows] == ["chunk_fn", "audit_rays"]  # largest first
        assert rows[0] == {"program": "chunk_fn", "traces": 1, "programs": 1,
                           "trace_seconds": 2.0, "lower_seconds": 0.5, "backend_seconds": 30.0}
        assert t.by_program(top=1) == rows[:1]

    def test_snapshot_holds_the_new_keys_beside_the_old(self):
        t = CompileTracker()
        _program(t, "chunk_fn", 2.0, 0.5, 30.0)
        snap = t.snapshot()
        assert {"programs", "compile_seconds", "cache_hits", "cache_misses"} <= set(snap)
        assert (snap["trace_seconds"], snap["lower_seconds"], snap["retrieval_seconds"]) == (2.0, 0.5, 0.0)
        assert snap["compile_seconds"] == 30.0 and snap["traces"] == 1
        assert snap["by_program"] == t.by_program(compiles.SNAPSHOT_ROWS)

    def test_rows_are_bounded(self):
        t = CompileTracker()
        n = compiles.MAX_PROGRAM_ROWS + 50
        for i in range(n):
            _program(t, f"f{i}", 1.0, 1.0, 1.0)
        rows = t.by_program()
        assert len(rows) == compiles.MAX_PROGRAM_ROWS + 1
        other = rows[0]  # fifty programs' seconds: the largest row
        assert other["program"] == compiles.OTHER and other["programs"] == 50
        assert sum(r["trace_seconds"] for r in rows) == t.trace_seconds == n
        assert len(t.snapshot()["by_program"]) == compiles.SNAPSHOT_ROWS

    def test_an_end_without_a_start_counts_as_outermost(self):
        """A trace that was open when the listeners were installed."""
        t = CompileTracker()
        t._on_duration(TRACE_EV, 1.5, fun_name="f")
        _program(t, "g", 0.5)
        assert (t.traces, t.trace_seconds) == (2, 2.0)

    def test_stages_into_a_span(self):
        t, rec = CompileTracker(), TraceRecorder()
        _program(t, "before", 1.0, 1.0, 1.0)
        with rec.span("render/chunk_dispatch+compile", chunk=0) as sp, t.stages_into(sp):
            _program(t, "chunk_fn", 2.0, 0.5, 30.0)
            t._on_event("/jax/compilation_cache/cache_misses")
        assert sp.args == {
            "chunk": 0, "compile_trace_seconds": 2.0, "compile_lower_seconds": 0.5,
            "compile_seconds": 30.0, "compile_retrieval_seconds": 0.0,
            "compile_cache_hits": 0, "compile_cache_misses": 1,
        }
        with rec.span("render/chunk_dispatch") as sp, t.stages_into(sp):
            pass
        assert set(sp.args.values()) == {0}


def test_jax_feeds_the_listener_and_a_second_render_adds_nothing():
    """Through jax.monitoring itself: a tiny render traces and lowers its
    chunk program, the first dispatch's span says so, a second render of
    the same scene traces, lowers, builds and loads nothing."""
    from tpu_pbrt.scenes import compile_api, make_cornell

    COMPILES.install()
    before = COMPILES.snapshot()
    scene, integ = compile_api(make_cornell(res=16, spp=4, integrator="path", maxdepth=3))
    integ.render(scene)
    first = COMPILES.snapshot()
    assert first["trace_seconds"] > before["trace_seconds"]
    assert first["lower_seconds"] > before["lower_seconds"]
    assert "chunk_fn" in {r["program"] for r in COMPILES.by_program()}
    dispatch = TRACE.spans("render/chunk_dispatch+compile")[-1]
    assert dispatch.args["compile_trace_seconds"] > 0 and dispatch.args["compile_lower_seconds"] > 0
    assert dispatch.args["compile_seconds"] > 0  # built by XLA or handed back by the cache
    assert dispatch.args["compile_trace_seconds"] <= dispatch.seconds

    integ.render(scene)
    second = COMPILES.snapshot()
    assert second == first
    again = TRACE.spans("render/chunk_dispatch+compile")[-1]
    assert again is not dispatch
    assert {v for k, v in again.args.items() if k.startswith("compile_")} == {0}


def test_a_span_is_placed_on_the_hosts_clock():
    rec = TraceRecorder()
    for rebase in (lambda: None, rec.reset, rec.set_clock):
        rebase()
        t_before = time.monotonic()
        with rec.span("x") as sp:
            t_in = time.monotonic()
            time.sleep(0.02)
            t_body_end = time.monotonic()
        t_after = time.monotonic()
        offset = time.monotonic() - rec.now()  # paired once, after the fact
        assert t_before - 1e-3 <= sp.start + offset <= t_in + 1e-3
        assert t_body_end - 1e-3 <= sp.start + offset + sp.seconds <= t_after + 1e-3


@pytest.fixture()
def harness():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run

    yield run
    sys.path.remove(os.path.join(ROOT, "benchmark"))


def test_the_programs_spans_tile_a_set_up(harness, tmp_path):
    """From the start of `compile_file` to the end of the warm-up render,
    on killeroo-class's `test` preset (the stream tracer, so the audit
    runs too): at least 95 % of the wall time lies under a span, by the
    benchmark's own reader of `setup_unattributed_s`. Work added to the
    set-up path under no span fails here."""
    from tpu_pbrt.scene.api import Options, compile_file

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx, _, config = harness.make_ctx(bench, "killeroo-frames-1chip", 35, 0.0, False, "test")
    desc = ctx["scene_writer"].build(config, ctx["seed"])
    path = ctx["write_scene"](desc, str(tmp_path), "scene")
    TRACE.reset()
    try:
        t0 = time.monotonic()
        scene, integ = compile_file(path, Options(quiet=True))
        integ.render(scene, max_seconds=1e-3)
        wall = time.monotonic() - t0
        reader = harness.load_module("metrics", "setup_unattributed_s")
        unattributed = reader.read({"t_start": t0, "setup_s": wall})
        names = {s.name for s in TRACE.spans()}
    finally:
        TRACE.reset()
    assert unattributed is not None and 0.0 <= unattributed <= 0.05 * wall, (unattributed, wall)
    assert {"scene/parse", "scene/compile", "scene/camera", "scene/shapes", "scene/assemble",
            "accel/sah_build", "scene/reorder", "scene/lights", "scene/materials",
            "scene/upload", "accel/treelet_pack", "scene/integrator", "render/prepare_chunks",
            "render/init_state", "render/capacity_audit", "render/chunk_dispatch+compile",
            "render/chunk_retire", "render/wave_drain+film_merge", "render/develop"} <= names
