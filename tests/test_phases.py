"""ISSUE 25: the device program's named phases, the stream-tracer work
counters, the span recorder's ring and the one trace reduction.

(a) scopes are metadata: the lowered chunk program equals, text for text
    without locations, the lowering with `jax.named_scope` patched away;
(b) its HLO holds every vocabulary scope the path uses;
(c) `obs/devtrace.py` on a recorded scoped TPU trace and on one without
    scopes;
(d) the stream counters count what `stream_traverse_stats` counts, ride the
    mesh psum, and leave the film bytewise alone;
(e) `TRACE`: ring, bound, `phase_seconds`, the Chrome JSON.
"""

import contextlib
import functools
import gzip
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_pbrt import config
from tpu_pbrt.obs import devtrace
from tpu_pbrt.obs import phases as ph
from tpu_pbrt.obs.trace import RING_SPANS, TRACE, TraceRecorder, validate_trace

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPED_GZ = os.path.join(HERE, "data", "scoped_tpu_1dev.xplane.pb.gz")
UNSCOPED_4DEV = os.path.join(
    os.path.dirname(HERE), "benchmark", "tests", "data", "tiny_tpu_4dev.xplane.pb"
)


def _stream_plan(n_dev: int):
    """A fresh small stream-tracer scene (2.2k triangles, 16x16, 2 spp) and
    its chunk plan, on one device or on a CPU mesh."""
    from tpu_pbrt.accel.stream import clear_traverse_caches
    from tpu_pbrt.parallel.mesh import make_mesh
    from tpu_pbrt.scenes import compile_api, make_killeroo_like

    clear_traverse_caches()  # the inner jits cache traced jaxprs by shape
    scene, integ = compile_api(make_killeroo_like(
        res=16, spp=2, integrator="path", maxdepth=3, n_theta=24, n_phi=48))
    mesh = make_mesh(n_dev) if n_dev > 1 else None
    return scene, integ, integ.prepare_chunks(scene, mesh)


def _lower(plan, scene):
    state = scene.film.init_state()
    st = plan.starts[0]
    if plan.mesh is not None:
        state = jax.device_put(state, NamedSharding(plan.mesh, P()))
        return plan.jfn.lower(state, scene.dev, st)
    return plan.jfn.lower(state, scene.dev, st[0], st[1])


@functools.lru_cache(maxsize=None)
def _shared_plan(n_dev: int):
    """`_stream_plan(n_dev)` once for the cases that only read it or render
    it as it stands: a case that patches jax or flips a knob builds its own."""
    return _stream_plan(n_dev)


@functools.lru_cache(maxsize=None)
def _shared_lowering(n_dev: int):
    """The chunk program of `_shared_plan(n_dev)`, traced and lowered once:
    three cases read the one-device text and two the mesh's."""
    scene, _, plan = _shared_plan(n_dev)
    return _lower(plan, scene)


def _scopes_in(text: str) -> set:
    found = set()
    for m in re.finditer(r'op_name="([^"]*)"|loc\("([^"]*)"', text):
        found.add(ph.deepest(m.group(1) or m.group(2)))
    return found - {ph.UNSCOPED}


# -- (a), (b): the scopes in the lowered program ------------------------------

POOL_PATH = {
    ph.CHUNK, ph.POOL_LOOP, ph.POOL_REGEN, ph.POOL_BOUNCE,
    ph.POOL_DEPOSIT, ph.TRACE_FUSED, ph.STREAM_LOOP, ph.STREAM_SEED,
    ph.STREAM_EXPAND, ph.STREAM_FLUSH, ph.STREAM_MERGE, ph.STREAM_FINALIZE,
    ph.SHADE_INTERACTION, ph.SHADE_EMIT, ph.SHADE_BSDF, ph.SHADE_NEE,
    ph.LIGHT_PICK, ph.LIGHT_SAMPLE, ph.LIGHT_PDF,
    ph.FILM_DEPOSIT,
}
MESH_ONLY = {ph.MESH_PSUM_FILM, ph.MESH_PSUM_AUX, ph.FILM_MERGE}


@pytest.mark.parametrize("n_dev", [1, 4], ids=["one_device", "mesh4"])
def test_hlo_holds_the_vocabulary(n_dev):
    text = _shared_lowering(n_dev).as_text(dialect="hlo", debug_info=True)
    found = _scopes_in(text)
    want = POOL_PATH | (MESH_ONLY if n_dev > 1 else set())
    assert want <= found, sorted(want - found)
    assert found <= set(ph.PHASES)
    # ISSUE 26: the per-wave lane permutation is gone, and its name with it
    assert "pool/compact" not in text
    assert "pool/regen/jit(cumsum)" in text  # the free-slot rank stands under pool/regen
    if n_dev == 1:
        assert not (MESH_ONLY & found)


@pytest.mark.parametrize("n_dev", [1, 4], ids=["one_device", "mesh4"])
def test_scopes_change_nothing_else(n_dev, monkeypatch):
    with_scopes = _shared_lowering(n_dev)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    scene0, _, plan0 = _stream_plan(n_dev)
    without = _lower(plan0, scene0)
    assert _scopes_in(without.as_text(debug_info=True)) == set()
    assert _scopes_in(with_scopes.as_text(debug_info=True)) >= POOL_PATH
    # as_text() prints no location and no metadata: what is left is the ops
    a, b = with_scopes.as_text().splitlines(), without.as_text().splitlines()
    assert len(a) == len(b) > 1000
    assert a == b


@pytest.mark.parametrize("path,want", [
    ("jit(chunk_fn)/chunk/pool/loop/while/body/pool/regen/jit(cumsum)/cumsum:", ph.POOL_REGEN),
    ("jit(chunk_fn)/chunk/while/body/pool/compact/sort:", ph.CHUNK),  # a name of no scope
    ("jit(chunk_fn)/chunk/while/body/pool/bounce/trace/fused/jit(stream_intersect_split)"
     "/stream/flush/while/body/stream/merge/sort:", ph.STREAM_MERGE),
    ("jit(chunk_fn)/chunk/while/cond/lt:", ph.CHUNK),
    ("jit(chunk_fn)/chunk/pool/loop/while/body/pool/bounce/shade/nee/light/pick/gather:", ph.LIGHT_PICK),
    ("jit(chunk_fn)/chunk/pool/loop/while/body/pool/bounce/shade/emit/light/pdf/sub:", ph.LIGHT_PDF),
    ("jit(chunk_fn)/chunk/shard_map/mesh/psum_aux/psum:", ph.MESH_PSUM_AUX),
    ("jit(chunk_body)/pooling/compact_fn/sort:", ph.UNSCOPED),
    ("jit(<lambda>)/jit(sort)/sort:", ph.UNSCOPED),
    ("", ph.UNSCOPED),
])
def test_deepest_scope(path, want):
    assert ph.deepest(path) == want
    assert ph.family(want) == want.split("/")[0]


# -- (c): the reduction on recorded traces ------------------------------------


def test_self_times_and_segments_arithmetic():
    # a while of 10 s that holds a sort of 4 s and a fusion of 3 s
    events = [("while", 0, 10), ("sort.1", 1, 5), ("fusion", 5, 8), ("copy", 11, 12)]
    assert sorted(devtrace.self_times(events)) == [
        ("copy", 11, 12, 1), ("fusion", 5, 8, 3), ("sort.1", 1, 5, 4), ("while", 0, 10, 3)]
    spans = [("render/frame", 0.0, 10.0), ("render/chunk_retire", 2.0, 6.0),
             ("render/develop", 8.0, 9.0)]
    assert sorted(devtrace._self_segments(spans)) == [
        ("render/chunk_retire", 2.0, 6.0), ("render/develop", 8.0, 9.0),
        ("render/frame", 0.0, 2.0), ("render/frame", 6.0, 8.0), ("render/frame", 9.0, 10.0)]
    gaps = [(1.0, 3.0), (5.0, 5.5), (9.5, 20.0)]
    starts = [g[0] for g in gaps]
    assert devtrace._overlap(2.0, 6.0, starts, gaps) == pytest.approx(1.5)
    assert devtrace._overlap(9.0, 10.0, starts, gaps) == pytest.approx(0.5)


def test_ops_without_a_path_are_placed_by_nesting():
    """XLA's own ops carry no tf_op: a container takes the scope its
    children share, a leaf the scope of what encloses it, the rest stays
    unscoped."""
    pre = "jit(chunk_fn)/chunk/pool/loop/while/body/"
    md = {
        "sort.1": [{"tf_op": pre + "pool/regen/jit(cumsum)/cumsum:", "hlo_category": "sort"}],
        "fusion.2": [{"tf_op": pre + "pool/deposit/cond/branch_0_fun/film/deposit/scatter-add:",
                      "hlo_category": "loop fusion"}],
        "gather.9": [{"tf_op": "gather:"}],  # a name XLA gave, not a jax path
    }
    events = [
        ("copy.0", 0.0, 1.0),          # nothing encloses it: unscoped
        ("while.7", 1.0, 11.0),        # encloses ops of two pool phases: pool/loop
        ("sort.1", 1.0, 4.0),
        ("copy.3", 4.0, 5.0),          # a leaf of the while's body: pool/loop too
        ("cond.4", 5.0, 9.0),          # its branch says the cond stands in pool/deposit
        ("fusion.2", 5.0, 8.0),
        ("gather.9", 8.0, 8.5),        # inside cond.4, no path of its own
    ]
    d = devtrace.reduce_device(lambda: iter(events), md)
    got = {k: (round(v["seconds"], 6), round(v["nested_seconds"], 6)) for k, v in d["phases"].items()}
    assert got == {
        ph.UNSCOPED: (1.0, 0.0),
        ph.POOL_LOOP: (3.0, 3.0),      # while.7's own 2 s + copy.3
        ph.POOL_REGEN: (3.0, 0.0),
        ph.POOL_DEPOSIT: (1.0, 1.0),   # cond.4's own 0.5 s + gather.9, both by nesting
        ph.FILM_DEPOSIT: (3.0, 0.0),   # fusion.2
    }
    assert d["busy_s"] == pytest.approx(11.0)
    assert sum(v["seconds"] for v in d["phases"].values()) == pytest.approx(d["busy_s"])
    # a line that is not in start order is sorted, not misread
    shuffled = events[3:] + events[:3]
    d2 = devtrace.reduce_device(lambda: iter(shuffled), md)
    assert {k: v["seconds"] for k, v in d2["phases"].items()} == pytest.approx(
        {k: v["seconds"] for k, v in d["phases"].items()})


def test_devtrace_on_the_recorded_scoped_trace(tmp_path):
    """tests/data/scoped_tpu_1dev.xplane.pb.gz: six pool waves of the
    configuration's `rehearsal` preset on a v5e (tests/data/
    make_scoped_trace.py; my chip run, PR 26: recorded again once the
    pool's per-wave compaction had gone). Numbers as recorded."""
    path = str(tmp_path / "scoped.xplane.pb")
    with gzip.open(SCOPED_GZ) as src, open(path, "wb") as dst:
        dst.write(src.read())
    red = devtrace.reduce_xplane(path, top=60)
    d = red["devices"]["/device:TPU:0"]
    assert red["n_devices"] == 1 and d["events"] == 16897
    assert red["busy_s"] == pytest.approx(0.029601178, rel=1e-9)
    # the phases sum to the busy union: nothing counted twice, nothing lost
    assert sum(r["seconds"] for r in red["phases"].values()) == pytest.approx(red["busy_s"], rel=1e-9)
    assert sum(red["families"].values()) == pytest.approx(red["busy_s"], rel=1e-9)
    want_us = {
        "stream/flush": 21263.6, "stream/merge": 6360.3, "stream/expand": 394.3,
        "film/deposit": 387.4, "stream/loop": 384.4, "pool/deposit": 199.0,
        "trace/fused": 117.3, "shade/nee": 100.3, "stream/seed": 73.4,
        "shade/bsdf": 68.8, "shade/interaction": 57.4, "pool/loop": 56.5,
        "stream/finalize": 47.3, "shade/emit": 35.0, "pool/regen": 21.8,
        "unscoped": 18.1, "pool/bounce": 14.2, "chunk": 2.2,
    }
    assert "pool/compact" not in red["phases"]  # PR 25's trace: 1159.3 us of 31616
    got_us = {k: v["seconds"] * 1e6 for k, v in red["phases"].items()}
    assert got_us == pytest.approx(want_us, abs=0.06)
    assert red["unscoped_share"] == pytest.approx(0.000610, abs=1e-6)
    assert red["families"]["stream"] / red["busy_s"] == pytest.approx(0.9636, abs=1e-4)
    # every sort of the program stands under the phase that asked for it
    sorts = {name: phase for phase, row in d["phases"].items()
             for name, _, _ in row["top"] if name.startswith("sort")}
    assert sorts == {
        "sort.81": "pool/deposit", "sort.80": "stream/seed", "sort.44": "stream/expand",
        "sort.64": "stream/flush", "sort.67": "stream/flush", "sort.82": "stream/merge",
    }  # one sort fewer than PR 25's seven: the pool's own is the deposit's alone
    # XLA's own loops and copies were placed by nesting, and the table says so.
    # The free-slot rank is XLA's too: it rewrites the cumsum into a two-level
    # reduce-window and names the pieces after the enclosing while, so they
    # stand under pool/loop by their own tf_op (9.4 us of its 56.5), not by nesting
    loop = d["phases"]["pool/loop"]
    assert loop["nested_seconds"] > 0.5 * loop["seconds"]
    assert [n for n, _, _ in loop["top"][:2]] == ["while.79", "reduce-window.28"]
    assert red["phases"]["stream/expand"]["nested_seconds"] == 0.0
    assert red["ambiguous"] == {}
    # the program's spans are in the trace, on the device's clock
    idle_us = {r["name"]: round(r["device_idle_s"] * 1e6, 1) for r in red["host_spans"]}
    assert idle_us == {
        "render/chunk_retire": 2326.7, "render/develop": 1653.7, "render/write_image": 1092.7,
        "render/prepare_chunks": 860.5, "render/chunk_dispatch+compile": 499.9,
        "render/wave_drain+film_merge": 56.4,
    }
    table = devtrace.format_table(red)
    assert "stream/flush" in table and "render/chunk_retire" in table


def test_devtrace_without_scopes_is_all_unscoped():
    red = devtrace.reduce_xplane(UNSCOPED_4DEV)
    assert red["n_devices"] == 4
    assert set(red["phases"]) == {ph.UNSCOPED}
    assert red["unscoped_share"] == pytest.approx(1.0)
    for d in red["devices"].values():
        assert d["phases"][ph.UNSCOPED]["seconds"] == pytest.approx(d["busy_s"], rel=1e-9)
    names = {r["name"] for r in red["host_spans"]}
    assert names == {"bench/frame", "bench/between"}
    frame = next(r for r in red["host_spans"] if r["name"] == "bench/frame")
    # the gaps between the ops lie inside the frame; so does the idle time
    # before its first op and after its last
    assert red["idle_s"] < frame["device_idle_s"] < frame["seconds"]
    assert frame["device_idle_s"] > 0.06  # three host sleeps of 20 ms
    assert "unscoped" in devtrace.format_table(red)
    # the decoder finds what the profiler stored for an op
    md = devtrace.read_op_metadata(UNSCOPED_4DEV)["/device:TPU:0"]
    sort = next(v for k, v in md.items() if k.startswith("%sort.15 "))
    assert sort[0]["tf_op"] == "jit(call_wrapped)/shard_map/jit(sort)/sort:"
    assert sort[0]["source"].endswith("make_trace.py:24")
    assert sort[0]["hlo_category"] == "sort"


def test_no_matrix_product_of_a_stream_scene_runs_at_the_default_precision():
    """On the TPU a float32 product at the default precision is ONE bf16
    pass (PERF.md, Findings PR 27: it snapped the camera's rays to a
    lattice coarser than a pixel). Every product left in a chunk program
    says what it needs."""
    text = _shared_lowering(1).as_text()
    dots = [line for line in text.splitlines() if "stablehlo.dot_general" in line]
    assert dots  # the stream tracer's leaf test and its one-hot fetches
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots), [
        line.strip()[:200] for line in dots if "HIGHEST" not in line]


# -- (d): the stream-tracer work counters -------------------------------------


def test_stream_work_is_what_traverse_stats_counts():
    from tpu_pbrt.accel.stream import stream_traverse_stats
    from tpu_pbrt.cameras import generate_rays
    from tpu_pbrt.integrators.common import scene_intersect_fused
    from tpu_pbrt.obs import counters as obs_counters

    scene, _, _ = _shared_plan(1)
    dev = scene.dev
    k = jnp.arange(512, dtype=jnp.int32)
    pf = jnp.stack([(k % 16).astype(jnp.float32) + 0.5,
                    ((k // 16) % 16).astype(jnp.float32) + 0.5], -1)
    o, d, _ = generate_rays(scene.camera, pf, jnp.zeros_like(pf))
    t_max = jnp.where(k % 7 == 0, -1.0, jnp.inf)  # some dead lanes, as a wave has
    totals = dict(rounds=0, pairs=0, leaf=0, drop=0, slots=0, back=0)
    ctr = jax.jit(obs_counters.zeros)()
    for n_cam in (256, 384):  # two waves with another camera/shadow split
        hit, tail, work = scene_intersect_fused(dev, o, d, t_max, n_cam=n_cam)
        alone = stream_traverse_stats(dev["tstream"], o, d, t_max)
        assert [int(x) for x in work] == [int(x) for x in alone]
        assert hit.prim.shape == (n_cam,) and tail.shape == (512 - n_cam,)
        totals["rounds"] += int(work.rounds)
        totals["pairs"] += int(work.pairs_expanded)
        totals["leaf"] += int(work.leaf_tests)
        totals["drop"] += int(work.pairs_dropped)
        # a trip of the flush runs whole blocks, filled or not
        assert int(work.block_slots) >= int(work.leaf_tests) and int(work.block_slots) % 32 == 0
        totals["slots"] += int(work.block_slots)
        totals["back"] += int(work.pairs_deferred)
        ctr = obs_counters.trace_update(ctr, work)
    host = obs_counters.to_host([ctr])
    assert host["stream_traversals"] == 2
    assert host["stream_rounds"] == totals["rounds"] > 0
    assert host["stream_pairs_expanded"] == totals["pairs"] > 0
    assert host["stream_leaf_tests"] == totals["leaf"] > 0
    assert host["stream_pairs_dropped"] == totals["drop"] == 0
    assert host["stream_block_slots"] == totals["slots"] >= totals["leaf"]
    assert host["stream_pairs_deferred"] == totals["back"] < totals["pairs"]
    # another acceleration structure, or telemetry killed: nothing to fold
    assert obs_counters.trace_update(ctr, None) is ctr
    assert obs_counters.trace_update(None, work) is None


@pytest.mark.parametrize("n_dev", [1, 4], ids=["one_device", "mesh4"])
def test_stream_counters_of_a_render(n_dev, monkeypatch):
    # the plan's own integrator and mesh: render() finds the chunk function
    # the lowering above traced in the integrator's slot
    scene, integ, plan = _shared_plan(n_dev)
    r = integ.render(scene, mesh=plan.mesh)
    c = r.stats["telemetry"]["counters"]
    # one traversal a wave on every device: the psum carried the counters
    assert c["stream_traversals"] == r.stats["n_waves"] > 0
    assert c["stream_rounds"] >= c["stream_traversals"]  # a wave with a live ray: EXPAND + FLUSH
    assert c["stream_pairs_expanded"] >= c["rays_traced"] == r.rays_traced
    assert c["stream_leaf_tests"] > 0
    assert c["stream_pairs_dropped"] == 0
    # summed over the frame's drains (and devices), filled or not
    assert c["stream_block_slots"] >= c["stream_leaf_tests"]
    assert 0 <= c["stream_pairs_deferred"] < c["stream_pairs_expanded"]
    tel = r.stats["telemetry"]
    assert tel["stream_trip_slots"] % tel["stream_block"] == 0
    assert c["stream_block_slots"] % tel["stream_trip_slots"] == 0
    # the stream tracer did all of it: nothing went the brute way
    assert c["brute_rays"] == c["brute_pairs_tested"] == 0
    # ISSUE 37: three light rows are the dense select's: the program carries
    # no light counter (it is the program it was, to the character)
    assert "light_picks" not in c and "light_table_reads" not in c
    if n_dev > 1:
        assert sum(r.stats["telemetry"]["wave_spread"]["per_device_waves"]) == c["stream_traversals"]
        return
    # the counters change no lane's path: the film without them, bytewise
    monkeypatch.setenv("TPU_PBRT_TELEMETRY", "0")
    config.reload()
    scene0, integ0, _ = _stream_plan(1)
    r0 = integ0.render(scene0)
    assert "telemetry" not in r0.stats
    assert r0.rays_traced == r.rays_traced and r0.stats["n_waves"] == r.stats["n_waves"]
    for a, b in zip(jax.device_get(r.film_state), jax.device_get(r0.film_state)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# -- (e): the span recorder ----------------------------------------------------


def test_spans_are_kept_without_a_path_and_the_ring_is_bounded(monkeypatch):
    monkeypatch.delenv("TPU_PBRT_TRACE_PATH", raising=False)
    config.reload()
    rec = TraceRecorder()
    assert not rec.enabled
    with rec.span("scene/parse") as outer:
        with rec.span("scene/compile", trace_id="t:1"):
            pass
    inner, parse = rec.spans("scene/")
    assert (inner.name, inner.parent, inner.trace_id) == ("scene/compile", "scene/parse", "t:1")
    assert parse is outer and parse.parent == ""
    assert parse.seconds >= inner.seconds >= 0.0
    assert parse.self_seconds == pytest.approx(parse.seconds - inner.seconds)
    assert parse.start <= inner.start
    assert rec.spans("render/") == [] and rec._events == []
    rec.async_begin("render/slice", id="s1", cat="slice")
    rec.async_end("render/slice", id="s1", cat="slice")
    rec.complete("render/backoff", 2500.0)
    assert [s.name for s in rec.spans("render/")] == ["render/slice", "render/backoff"]
    assert rec.spans("render/backoff")[0].seconds == pytest.approx(0.0025)
    for i in range(RING_SPANS + 50):
        with rec.span(f"fill/{i}"):
            pass
        rec.async_begin("leak", id=str(i))  # begins whose end never comes
    assert len(rec.spans()) == RING_SPANS
    assert rec.spans()[-1].name == f"fill/{RING_SPANS + 49}"
    assert len(rec._async_open) <= RING_SPANS
    assert rec.maybe_export() is None
    rec.reset()
    assert rec.spans() == []


def test_render_spans_feed_phase_seconds_and_the_chrome_json(tmp_path, monkeypatch):
    from tpu_pbrt.scenes import compile_api, make_cornell

    path = str(tmp_path / "trace.json")
    monkeypatch.setenv("TPU_PBRT_TRACE_PATH", path)
    config.reload()
    TRACE.reset()
    try:
        scene, integ = compile_api(make_cornell(res=16, spp=4, integrator="path", maxdepth=3))
        r = integ.render(scene)
        by_name = {}
        for sp in TRACE.spans("render/"):
            by_name[sp.name] = by_name.get(sp.name, 0.0) + sp.seconds
        phases = r.stats["phase_seconds"]
        assert set(phases) <= {"dispatch_compile", "dispatch", "dispatch_ahead",
                               "device_wait", "deposit_develop", "checkpoint"}
        assert {"dispatch_compile", "device_wait", "deposit_develop"} <= set(phases)
        # each region is timed once, by its span: the phase IS the spans' sum
        assert phases["dispatch_compile"] == pytest.approx(
            by_name["render/chunk_dispatch+compile"], abs=2e-6)
        assert phases["device_wait"] == pytest.approx(
            by_name["render/chunk_retire"] + by_name["render/wave_drain+film_merge"], abs=2e-6)
        assert phases["deposit_develop"] == pytest.approx(
            by_name["render/develop"] + by_name["render/write_image"], abs=2e-6)
        assert "render/prepare_chunks" in by_name
        assert validate_trace(path) == []
        names = {e["name"] for e in json.load(open(path))["traceEvents"]}
        assert {"render/chunk_retire", "render/develop", "scene/upload",
                "accel/sah_build"} <= names
    finally:
        TRACE.reset()
