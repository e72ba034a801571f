"""crown-geometry (ISSUE 31): the configuration's scene writer, and its
`test` preset through the normal path WITH the two branches a
3.5-million-triangle scene takes in the stream tracer: EXPAND's native
gather (a top tree of more than 512 nodes) and FLUSH's pair sort (4,096
treelets or more under the pool's wave). No scene a test can render reaches
either by its size, so the two thresholds are moved for this file
(`_ONEHOT_MAX_NODES`, `_flush_key_packed`); tests/test_stream_oracle.py holds
the same branches to the brute-force oracle ray by ray.
"""

import contextlib
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "crown-geometry-frames-1chip"


@pytest.fixture(scope="module")
def harness():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run

    yield run
    sys.path.remove(os.path.join(ROOT, "benchmark"))


@contextlib.contextmanager
def large_scene_branches():
    """Both thresholds moved so that every pack takes the gather fetch and
    the pair sort; the tracer's jit caches dropped around it."""
    import tpu_pbrt.accel.stream as st

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(st, "_ONEHOT_MAX_NODES", 0)
        mp.setattr(st, "_flush_key_packed", lambda n_treelets, ray_bits: False)
        st.clear_traverse_caches()
        try:
            yield
        finally:
            mp.undo()
            st.clear_traverse_caches()


def _config(harness, preset="test"):
    config = harness.load_json(ROOT, "benchmark", "configs", "crown-geometry.json")
    return harness.merge(config, config["presets"][preset]) if preset else config


def test_scene_writer_is_a_pure_function_and_the_seed_moves_reflectances_only(harness):
    config = _config(harness)
    build = harness.load_module("scenes", "crown_geometry").build
    a, again, b = build(config, 11), build(config, 11), build(config, 2**31 + 11)
    amp = config["scene_params"]["seeded"]["kd"]
    assert amp <= 0.02
    assert len(a["meshes"]) == 2 + 1 + sum(band["count"] for band in config["scene_params"]["bands"]) >= 35
    moved = 0
    for ma, mg, mb in zip(a["meshes"], again["meshes"], b["meshes"]):
        for k in ("P", "indices", "N"):
            for other in (mg, mb):
                assert (ma[k] is None and other[k] is None) or np.array_equal(ma[k], other[k])
        assert ma["name"] == mb["name"] and ma["ply"] == mb["ply"] and ma["L"] == mb["L"]
        np.testing.assert_array_equal(ma["Kd"], mg["Kd"])
        assert np.abs(np.asarray(ma["Kd"]) - np.asarray(mb["Kd"])).max() <= 2 * amp + 1e-12
        moved += int(not np.array_equal(ma["Kd"], mb["Kd"]))
    assert moved == len(a["meshes"]) - 1  # all but the light's own surface
    for k in ("camera", "film", "spp", "maxdepth", "sampler", "integrator", "point_lights"):
        assert a[k] == b[k]


def test_the_files_geometry_is_what_the_issue_asks_for(harness):
    """At the cell's own size, from the file's numbers alone (no mesh is
    built): the count, at least 32 band meshes, each through PLY."""
    config = _config(harness, preset="")
    p = config["scene_params"]
    tris = lambda m: (m["n_theta"] - 1) * m["n_phi"] * 2  # noqa: E731
    total = tris(p["body"]) + sum(b["count"] * tris(b) for b in p["bands"]) + 4
    assert total == config["triangles"] and 3_400_000 <= total <= 3_600_000
    assert sum(b["count"] for b in p["bands"]) >= 32
    assert all(tris(b) > p["ply_over_triangles"] for b in p["bands"]) and p["ply_over_triangles"] <= 5000
    # the bands' places and sizes are the preset's too: the next test builds them
    preset = _config(harness)["scene_params"]["bands"]
    assert [{k: b[k] for k in ("count", "polar_deg", "phase", "radius")} for b in p["bands"]] == [
        {k: b[k] for k in ("count", "polar_deg", "phase", "radius")} for b in preset]
    assert config["reduced_why"]["pixelsamples"] and config["published"]["pixelsamples"] == 64


def test_the_preset_writes_meshes_that_run_through_each_other(harness):
    desc = harness.load_module("scenes", "crown_geometry").build(_config(harness), 3)
    boxes = [(m["P"].min(axis=0), m["P"].max(axis=0)) for m in desc["meshes"] if m["name"] not in ("light", "ground")]
    over = lambda a, b: bool(np.all(a[0] <= b[1]) and np.all(b[0] <= a[1]))  # noqa: E731
    body, bands = boxes[0], boxes[1:]
    assert all(over(body, b) for b in bands)
    assert all(sum(over(a, b) for b in bands if b is not a) >= 2 for a in bands)
    assert sum(m["ply"] for m in desc["meshes"]) == 1 + len(bands)


@pytest.fixture(scope="module")
def sound(harness):
    """One frame of the cell at its `test` preset through the `frames`
    driver (a .pbrt file + binary PLYs -> compile_file -> render), compared
    as the benchmark compares it."""
    from tpu_pbrt.obs.trace import TRACE

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    ctx, driver, config = harness.make_ctx(bench, CELL, 5, 0.0, False, "test")
    try:
        with large_scene_branches():
            driver.setup(ctx)
            plan = ctx["_integ"].prepare_chunks(ctx["_scene"])
            text = plan.jfn.lower(ctx["_scene"].film.init_state(), ctx["_scene"].dev, *plan.starts[0]).as_text()
            spans = {name: TRACE.spans(name)[-1] for name in ("accel/treelet_pack", "scene/upload")}
            driver.window(ctx)
        frame = ctx["frames"][0]
        metrics = {name: harness.load_module("metrics", name).read(ctx)
                   for name in ("pairs_expanded_per_ray", "scene_resident_mb")}
        image, weight = driver.film(ctx)
        driver.release(ctx)
        pix, ref_px = harness.reference_pixels(ctx, config)
    finally:
        shutil.rmtree(ctx["work_dir"], ignore_errors=True)

    def verdict(image, weight):
        """`run.py::check_film`'s comparison, on a film handed to it."""
        compare = harness.load_module("", "compare")
        numbers = compare.film_numbers(image, weight, int(config["pixelsamples"]))
        numbers.update(harness.film_gaps(config, pix, image[pix[:, 1], pix[:, 0]], ref_px))
        return compare.verdict(numbers, config["check"]["limits"])

    return {"verdict": verdict, "film": (image, weight), "stats": frame["stats"], "rays": frame["rays_traced"],
            "failed": ctx["failed"], "text": text, "spans": spans, "metrics": metrics}


def test_crown_geometry_is_the_reference_within_the_presets_limits(sound):
    correct, rows = sound["verdict"](*sound["film"])
    assert correct and sound["failed"] == 0, rows


def test_crown_geometry_with_the_radiance_fault_is_outside_them(harness, sound):
    """+10 % radiance, planted in the film the run produced as `control.py
    --faults` plants it (benchmark/tests/test_correct_crown_geometry.py
    plants it where radiance is deposited, by hand)."""
    image, weight = harness.load_module("", "control").plant("altered10", *sound["film"])
    correct, rows = sound["verdict"](image, weight)
    over = {k for k, row in rows.items() if row["value"] > row["limit"]}
    assert not correct and over & {"mean_gap", "tile_gap"} and not over & {"spp_gap", "nonfinite"}, rows


def test_counters_reconcile_and_the_branch_facts_are_in_the_telemetry(sound):
    tel = sound["stats"]["telemetry"]
    c = tel["counters"]
    assert c["rays_traced"] == sound["rays"] > 0
    assert c["stream_pairs_dropped"] == 0 and c["stream_pairs_expanded"] > 0
    assert c["stream_block_slots"] >= c["stream_leaf_tests"] > 0 and c["brute_rays"] == 0
    assert tel["stream_fetch"] == "gather" and tel["stream_flush_key"] == "pair"
    assert tel["stream_block"] in (32, 64, 128) and c["stream_block_slots"] % tel["stream_trip_slots"] == 0
    assert tel["stream_trip_slots"] % tel["stream_block"] == 0
    assert tel["stream_treelets"] > 8 and tel["stream_top_nodes"] > 1
    # the same facts stand on the scene compiler's span, with the bytes by table
    packed, upload = sound["spans"]["accel/treelet_pack"].args, sound["spans"]["scene/upload"].args
    for k in ("stream_top_nodes", "stream_treelets", "stream_fetch", "stream_flush_key"):
        assert packed[k] == tel[k]
    # the span's height is the widest wave's (2^19 rays), the telemetry's this plan's
    assert packed["stream_block"] in (32, 64, 128) and packed["stream_trip_slots"] == 4096
    tables = upload["scene_resident_bytes"]
    assert tables["tstream.featT"] == tel["stream_treelets"] * 16 * 2048 * 4
    assert {"tri_verts", "tri_verts9T", "tri_sh16", "tri_normals"} <= set(tables)
    assert sound["metrics"]["scene_resident_mb"] == pytest.approx(sum(tables.values()) / 1e6)
    assert sound["metrics"]["pairs_expanded_per_ray"] == pytest.approx(c["stream_pairs_expanded"] / sound["rays"])


def test_the_thresholds_read_what_the_chip_will_meet():
    """The three facts at the cell's own numbers (3,263 top nodes and 10,234
    treelets under the pool's 2 x 262,144-ray wave), and killeroo-class's."""
    from tpu_pbrt.accel.stream import (
        BLOCK, FUSED_WAVE_RAYS, _flush_block, _flush_key_packed, _flush_trip, _ray_bits, _sizes, _use_onehot)

    rb = _ray_bits(FUSED_WAVE_RAYS)
    assert rb == 19
    assert not _use_onehot(3263) and not _flush_key_packed(10234, rb)
    assert _use_onehot(512) and _flush_key_packed(4095, rb) and not _flush_key_packed(4096, rb)
    assert _flush_key_packed(2047, _ray_bits(1 << 20)) and not _flush_key_packed(2048, _ray_bits(1 << 20))
    # the flush's block: 4 slabs of pairs over the treelets that share them
    # (51 pairs a run here; killeroo's ~380 treelets 1,380 on one chip, 345
    # on a mesh device of a quarter of the pool)
    slab = _sizes(FUSED_WAVE_RAYS)[0]
    assert slab == 131072 and _sizes(FUSED_WAVE_RAYS // 4)[0] == 32768
    assert _flush_block(10234, slab) == 32
    assert _flush_block(380, slab) == _flush_block(380, 32768) == _flush_block(4096, slab) == 128
    assert _flush_block(4097, slab) == 64 and _flush_block(1 << 20, slab) == 32
    # the flush's trip: an eighth of a slab of ray slots, so 4 slabs of pairs
    # are 32 trips or more, within 16 and 32 blocks of BLOCK: the pool's wave
    # and a mesh device's of a quarter of it take the ceiling, the narrowest
    # slab the floor (half of it)
    assert _flush_trip(slab) == _flush_trip(32768) == _flush_trip(1 << 30) == 32 * BLOCK == 4096
    assert _flush_trip(32767) == _flush_trip(4096) == _flush_trip(1) == 16 * BLOCK == 2048
    for s in (1, 100, 4096, 5000, 12345, 32768, 40000, 100000, slab, 1 << 22):
        t = _flush_trip(s)
        assert t & (t - 1) == 0 and t % BLOCK == 0 and (4 * s >= 32 * t or t == 16 * BLOCK)


def test_the_cells_chunk_program_holds_no_product_at_the_default_precision(sound):
    dots = [line for line in sound["text"].splitlines() if "stablehlo.dot_general" in line]
    assert dots  # the leaf product stays; the one-hot fetch is gone with the gather
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots), [
        line.strip()[:200] for line in dots if "HIGHEST" not in line]
    assert "stablehlo.convolution" not in sound["text"]
