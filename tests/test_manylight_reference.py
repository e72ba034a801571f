"""killeroo-manylight (ISSUE 37) at its `test` preset (32x32, 64 spp, a
sphere of 288 triangles, 32 fixtures of 8 triangles: 256 light rows, above
the dense select's 16): the program's film through a `.pbrt` file,
`compile_file` and the pool against the plain reference, under the preset's
limits; the bfloat16 control outside them; the strategy the file gets
(upstream's default: it names none) built and counted; and the lowered pool
program read for what the issue took out of it: no value of lanes x lights.
"""

import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "killeroo-manylight-frames-1chip"
SEED = 2_000_000_011


@pytest.fixture(scope="module")
def harness():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run

    yield run
    sys.path.remove(os.path.join(ROOT, "benchmark"))


@pytest.fixture(scope="module")
def rendered(harness):
    """One frame of the preset through the benchmark's own driver ->
    (ctx, config, image, weight, the scene's light facts)."""
    from tpu_pbrt.obs.trace import TRACE

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx, driver, config = harness.make_ctx(bench, CELL, SEED, 0.0, False, "test")
    driver.setup(ctx)
    scene, integ = ctx["_scene"], ctx["_integ"]
    plan = integ.prepare_chunks(scene)
    lowered = plan.jfn.lower(scene.film.init_state(), scene.dev, *plan.starts[0]).as_text()
    facts = {
        "span": dict(TRACE.spans("scene/light_distribution")[-1].args),
        "resident": dict(TRACE.spans("scene/upload")[-1].args["scene_resident_bytes"]),
        "strategy_built": scene.light_strategy_built, "n_lights": scene.n_lights,
        "pool": plan.pool, "lowered": lowered,
        "file": open(os.path.join(ctx["work_dir"], "scene.pbrt")).read(),
    }
    driver.window(ctx)
    image, weight = driver.film(ctx)
    driver.release(ctx)
    return ctx, config, image, weight, facts


def test_the_file_names_no_strategy_and_gets_spatial(rendered):
    _, _, _, _, facts = rendered
    assert "lightsamplestrategy" not in facts["file"] and "\nLightSource" not in facts["file"]
    assert facts["file"].count("AreaLightSource") == 32
    span = facts["span"]
    assert span["strategy_asked"] == span["strategy_built"] == facts["strategy_built"] == "spatial"
    assert span["light_rows"] == facts["n_lights"] == 256 and span["voxels"] == 512
    assert span["table_bytes"] == 512 * 256 * 4
    # the pick's plan (ISSUE 38): 256 rows are 8 bits, two levels of 15
    # pivots over 512 and 512 x 16 columns, no binary step
    assert span["pick_levels"] == 2 and span["pick_tail_steps"] == 0
    assert span["pivot_bytes"] == 15 * 4 * (512 + 512 * 16)
    # the tables by name among the scene's resident bytes, the table an argument of the program
    assert facts["resident"]["light_pick"] >= span["table_bytes"] + span["pivot_bytes"]
    assert facts["resident"]["light"] >= 256 * 15 * 4
    assert "dense<" not in "".join(line for line in facts["lowered"].split("\n") if "131072xf32" in line)


def test_film_against_the_plain_reference(rendered, harness):
    ctx, config, image, weight, _ = rendered
    compare = harness.load_module("", "compare")
    numbers = compare.film_numbers(image, weight, int(config["pixelsamples"]))
    assert numbers["spp_gap"] == 0.0 and numbers["nonfinite"] == 0.0
    pix, ref_px = harness.reference_pixels(ctx, config)
    numbers.update(harness.film_gaps(config, pix, image[pix[:, 1], pix[:, 0]], ref_px))
    ok, rows = compare.verdict(numbers, config["check"]["limits"])
    assert ok, rows
    frame = ctx["frames"][0]
    assert frame["ok"] and frame["stats"]["regen"]  # through the pool wavefront


def test_control_bfloat16_is_outside_the_limits(rendered, harness):
    import jax.numpy as jnp

    ctx, config, _, _, _ = rendered
    compare = harness.load_module("", "compare")
    limits = {k: config["check"]["limits"][k] for k in ("mean_gap", "tile_gap")}
    pix, ref_px = harness.reference_pixels(ctx, config)
    _, ctl_px = harness.reference_pixels(ctx, config, key_offset=1, dtype=jnp.bfloat16)
    ok, rows = compare.verdict(harness.film_gaps(config, pix, ctl_px, ref_px), limits)
    assert not ok, rows


def test_light_counters_and_the_four_metrics(rendered, harness):
    from tpu_pbrt.core import lights_dev as ld

    ctx, _, _, _, facts = rendered
    c = ctx["frames"][0]["stats"]["telemetry"]["counters"]
    assert 0 < c["light_picks"] < c["rays_traced"]
    # two levels of 15 pivots (no binary step at 256 rows) and one packed
    # row a pick; two reads of the table and five of the row a valid
    # vertex, of which some may not scatter
    pick, emit = 2 * ld.PIVOTS + ld.ROW_WIDTH, 2 + 5
    assert c["light_picks"] * (pick + emit) <= c["light_table_reads"] <= c["light_picks"] * (pick + 2 * emit)
    read = lambda name: harness.load_module("metrics", name).read(ctx)  # noqa: E731
    assert pick + emit <= read("light_reads_per_pick") <= pick + 2 * emit
    assert 0.3 < read("light_picks_per_ray") < 1.0
    assert 0.0 < read("light_distribution_s") < 30.0
    assert read("light_table_mb") == pytest.approx((facts["resident"]["light"] + facts["resident"]["light_pick"]) / 1e6)


def _lanes_by_lights(text: str, lanes: int, lights: int):
    """Tensor types of a lowered program that hold lanes x lights elements,
    or a lane axis beside a light axis."""
    found = set()
    for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]\w*>", text):
        shape = [int(d) for d in dims.split("x") if d]
        n = int(np.prod(shape))
        if n == lanes * lights or (lights in shape and any(d >= lanes for d in shape)):
            found.add(dims)
    return found


def test_no_value_of_lanes_times_lights_in_the_pool_program(rendered, harness, monkeypatch):
    """The acceptance criterion, read off the lowered text as
    tests/test_tpu_layout.py reads the node gather's; and the same reading
    FINDS the former expression when it is planted back. On the preset with
    one fixture more (33: 264 light rows), a count no other axis of the
    program has."""
    import jax.numpy as jnp

    from tpu_pbrt.core import lights_dev as ld
    from tpu_pbrt.scene.api import Options, compile_file

    ctx = rendered[0]
    config = harness.merge(ctx["config"], {"scene_params": {"fixtures": {"back": 3}}})
    path = ctx["write_scene"](ctx["scene_writer"].build(config, SEED), ctx["work_dir"], "scene264")

    def lowered():
        scene, integ = compile_file(path, Options(quiet=True))
        assert scene.n_lights == 264 and scene.light_strategy_built == "spatial"
        plan = integ.prepare_chunks(scene)
        assert plan.pool != 512  # the table itself is voxels x lights
        return plan.jfn.lower(scene.film.init_state(), scene.dev, *plan.starts[0]).as_text(), plan.pool

    text, lanes = lowered()
    assert not _lanes_by_lights(text, lanes, 264)

    def row_gather_and_count(self, u, p):  # the program before ISSUE 37
        row = self.cdf.reshape(-1, self.n)[self._voxel(p)]
        idx = jnp.minimum(jnp.sum((u[..., None] >= row).astype(jnp.int32), axis=-1), self.n - 1)
        at = jnp.take_along_axis(row, idx[..., None], -1)[..., 0]
        prev = jnp.where(idx > 0, jnp.take_along_axis(row, jnp.maximum(idx - 1, 0)[..., None], -1)[..., 0], 0.0)
        return idx, jnp.maximum(at - prev, 1e-12)

    monkeypatch.setattr(ld.SpatialLightDistribution, "sample_discrete_at", row_gather_and_count)
    text, lanes = lowered()
    assert _lanes_by_lights(text, lanes, 264)
