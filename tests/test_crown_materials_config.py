"""crown-materials: crown-geometry's meshes in gold and silver
microfacet metal and glass gems under an HDR environment map. The scene
writer and its description; the `test` preset through the normal path (a
.pbrt file, binary PLYs and a PFM map -> compile_file -> the pool, with the
stream tracer's two large-scene branches moved on, as
tests/test_crown_geometry_config.py moves them) against the configuration's
own plain reference under the preset's limits; the reference's bfloat16
control outside them; the counters and spans the configuration adds. The
three faults planted in the program are tests/test_crown_materials_faults.py.
"""

import ast
import contextlib
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "crown-materials-frames-1chip"
SEED = 2_147_483_777


@pytest.fixture(scope="module")
def harness():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run

    yield run
    sys.path.remove(os.path.join(ROOT, "benchmark"))


def _config(harness, preset="test"):
    config = harness.load_json(ROOT, "benchmark", "configs", "crown-materials.json")
    return harness.merge(config, config["presets"][preset]) if preset else config


@contextlib.contextmanager
def large_scene_branches():
    """The stream tracer's gather fetch and pair sort, as a 3.5 M-triangle
    scene takes them; the tracer's jit caches dropped around it."""
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


def _frame(harness, seed=SEED, lower=False):
    """One frame of the `test` preset through the `frames` driver -> (ctx,
    config, image, weight, the chunk program's text with its scopes)."""
    import jax

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    ctx, driver, config = harness.make_ctx(bench, CELL, seed, 0.0, False, "test")
    jax.clear_caches()
    try:
        with large_scene_branches():
            driver.setup(ctx)
            text = None
            if lower:
                scene, integ = ctx["_scene"], ctx["_integ"]
                plan = integ.prepare_chunks(scene)
                text = plan.jfn.lower(scene.film.init_state(), scene.dev, *plan.starts[0]).as_text(debug_info=True)
                ctx["dev_keys"] = set(scene.dev)
            driver.window(ctx)
        image, weight = driver.film(ctx)
        driver.release(ctx)
    finally:
        shutil.rmtree(ctx["work_dir"], ignore_errors=True)
        jax.clear_caches()
    return ctx, config, image, weight, text


@pytest.fixture(scope="module")
def sound(harness):
    """The program's frame and the plain reference on the seed's pixels."""
    ctx, config, image, weight, text = _frame(harness, lower=True)
    pix, ref_px = harness.reference_pixels(ctx, config)
    return {"ctx": ctx, "config": config, "film": (image, weight), "text": text, "pix": pix, "ref": ref_px}


def _verdict(harness, sound, image, weight):
    """`run.py::check_film`'s comparison, on a film handed to it."""
    compare = harness.load_module("", "compare")
    config, pix = sound["config"], sound["pix"]
    numbers = compare.film_numbers(image, weight, int(config["pixelsamples"]))
    numbers.update(harness.film_gaps(config, pix, image[pix[:, 1], pix[:, 0]], sound["ref"]))
    return compare.verdict(numbers, config["check"]["limits"])


def test_scene_writer_imports_the_geometry_and_the_seed_moves_values_only(harness):
    config = _config(harness)
    writer = harness.load_module("scenes", "crown_materials")
    assert "def geometry" not in open(writer.__file__).read()  # imported, not copied
    a, again, b = writer.build(config, 11), writer.build(config, 11), writer.build(config, 2**31 + 11)
    p = config["scene_params"]
    assert [m["name"] for m in a["meshes"]] == ["ground", "body"] + [
        f"b{i}m{k}" for i, band in enumerate(p["bands"]) for k in range(band["count"])]
    geo = harness.load_module("scenes", "crown_geometry").geometry(p)
    kinds = {}
    for ma, mg, mb in zip(a["meshes"], again["meshes"], b["meshes"]):
        for k in ("P", "indices", "N"):
            for other in (mg, mb):
                assert (ma[k] is None and other[k] is None) or np.array_equal(ma[k], other[k])
        assert ma["material"] == mg["material"]
        if ma["name"] != "ground":
            assert ma["material"] == mb["material"]
        kinds.setdefault(ma["material"]["type"], []).append(ma["name"])
    for (name, V, F, N, _), m in zip(geo, a["meshes"][1:]):
        assert name == m["name"] and np.array_equal(V, m["P"]) and np.array_equal(F, m["indices"])
    assert kinds["matte"] == ["ground"] and len(kinds["glass"]) == 24
    assert sorted(kinds["metal"]) == sorted(["body"] + [f"b1m{k}" for k in range(14)])
    kd_a, kd_b = (np.asarray(x["meshes"][0]["material"]["Kd"]) for x in (a, b))
    assert 0 < np.abs(kd_a - kd_b).max() <= 2 * p["seeded"]["kd"] + 1e-12
    ratio = b["env"]["map"] / a["env"]["map"]
    assert np.allclose(ratio, ratio.flat[0], rtol=1e-6) and ratio.flat[0] != 1.0
    assert abs(ratio.flat[0] - 1) <= 2 * 1.02 * p["seeded"]["env_scale"]
    np.testing.assert_array_equal(a["env"]["map"], again["env"]["map"])
    assert a["env"]["map"].shape == (64, 128, 3) and a["env"]["rotate"] == b["env"]["rotate"]
    for k in ("camera", "film", "spp", "maxdepth", "sampler", "integrator"):
        assert a[k] == b[k]


def test_the_file_states_the_sizes_materials_and_modules(harness):
    """At the cell's own size, from the file's numbers alone: the triangle
    count, the map's 2048x1024, the materials, the modules it names."""
    config = _config(harness, preset="")
    p = config["scene_params"]
    tris = lambda m: (m["n_theta"] - 1) * m["n_phi"] * 2  # noqa: E731
    assert tris(p["body"]) + sum(b["count"] * tris(b) for b in p["bands"]) + 2 == config["triangles"] == 3_499_922
    assert (p["env"]["width"], p["env"]["height"]) == (config["env_width"], config["env_height"]) == (2048, 1024)
    mats = p["materials"]
    assert mats["body"] == {"type": "metal", "eta": [0.143, 0.374, 1.442], "k": [3.983, 2.386, 1.603],
                            "roughness": 0.05}
    assert mats["bands"][1]["uroughness"] == 0.3 and mats["bands"][1]["vroughness"] == 0.05
    assert mats["bands"][0] == mats["bands"][2] == {"type": "glass", "eta": 1.5, "Kr": [1, 1, 1], "Kt": [1, 1, 1]}
    assert config["reduced"] == ["pixelsamples", "xresolution", "yresolution", "maxdepth"]
    assert set(config["reduced_why"]) == set(config["reduced"]) and config["published"]["pixelsamples"] == 64
    assert config["scene_description"] == config["reference"] == "crown_materials"
    modules = harness.config_modules(config)
    ref = modules["reference"].__file__
    assert ref.endswith(os.path.join("references", "crown_materials.py"))
    imported = set()
    for node in ast.walk(ast.parse(open(ref).read())):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert "tpu_pbrt" not in imported and imported <= {"__future__", "importlib", "math", "os", "jax", "numpy"}


def test_the_description_writes_the_same_bytes_and_the_map_the_program_reads(harness, tmp_path):
    from tpu_pbrt.utils.imageio import read_image

    config = _config(harness)
    desc = harness.load_module("scenes", "crown_materials").build(config, 3)
    write = harness.config_modules(config)["scene_description"].write_scene
    files = []
    for sub in ("a", "b"):
        write(desc, str(tmp_path / sub), "scene")
        files.append({f: open(tmp_path / sub / f, "rb").read() for f in sorted(os.listdir(tmp_path / sub))})
    assert files[0] == files[1] and "scene-env.pfm" in files[0]
    np.testing.assert_array_equal(read_image(str(tmp_path / "a" / "scene-env.pfm")), desc["env"]["map"])
    text = files[0]["scene.pbrt"].decode()
    assert text.count('LightSource "infinite"') == 1 and "Rotate -90 1 0 0" in text
    assert text.count('Material "glass"') == 24 and text.count('Material "metal"') == 15
    assert text.count("uroughness") == 14 and text.count('Material "matte"') == 1
    assert "AreaLightSource" not in text and 'LightSource "point"' not in text


def test_crown_materials_is_the_reference_within_the_presets_limits(harness, sound):
    correct, rows = _verdict(harness, sound, *sound["film"])
    frame = sound["ctx"]["frames"][0]
    assert correct and sound["ctx"]["failed"] == 0, rows
    assert frame["ok"] and frame["stats"]["regen"]  # through the pool wavefront


def test_control_bfloat16_is_outside_the_limits(harness, sound):
    """The configuration's reference computed one precision lower
    (`control.py`'s control), in the program's place. At 8 of the preset's
    64 reference paths a pixel: bfloat16 runs emulated on the CPU, 3 s a
    path a pixel; its film reads 20 % darker, the noise of 8 paths a pixel
    about 7 % (the float32 reference against itself at 8)."""
    import jax.numpy as jnp

    compare = harness.load_module("", "compare")
    config = sound["config"]
    limits = {k: config["check"]["limits"][k] for k in ("mean_gap", "tile_gap")}
    cheap = harness.merge(config, {"check": {"ref_spp": 8}})
    _, ctl_px = harness.reference_pixels(sound["ctx"], cheap, key_offset=1, dtype=jnp.bfloat16)
    ok, rows = compare.verdict(harness.film_gaps(config, sound["pix"], ctl_px, sound["ref"]), limits)
    assert not ok and rows["mean_gap"]["value"] > 2 * limits["mean_gap"], rows


def test_counters_metrics_spans_and_the_anisotropic_frame(harness, sound):
    """The five counters, the three metrics that read them, both spans in
    the chunk program, and the dpdu tangent table an anisotropic lobe
    reads (pbrt's shading frame)."""
    from tpu_pbrt.core import smalltab

    ctx = sound["ctx"]
    c = ctx["frames"][0]["stats"]["telemetry"]["counters"]
    p = _config(harness)["scene_params"]["env"]
    h, w = p["height"], p["width"]
    (lm, tm), (lc, tc) = smalltab.pick_plan(h, 1), smalltab.pick_plan(w, h)
    per_sample = smalltab.PIVOTS * (lm + lc) + tm + tc + 4
    assert 0 < c["env_samples"] <= c["shading_vertices"] and 0 < c["env_escapes"] < c["rays_traced"]
    assert c["env_table_reads"] == c["env_samples"] * per_sample + 2 * c["env_escapes"]
    assert 0 < c["delta_vertices"] < c["shading_vertices"]
    read = lambda name: harness.load_module("metrics", name).read(ctx)  # noqa: E731
    assert per_sample < read("env_reads_per_sample") < per_sample + 2 * c["env_escapes"] / c["env_samples"] + 1e-9
    assert read("delta_vertex_pct") == pytest.approx(100 * c["delta_vertices"] / c["shading_vertices"])
    assert read("escapes_per_ray") == pytest.approx(c["env_escapes"] / ctx["frames"][0]["rays_traced"])
    assert "light/env_sample" in sound["text"] and "light/env_le" in sound["text"]
    assert "tri_tanT" in ctx["dev_keys"]
