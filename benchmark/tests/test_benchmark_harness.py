"""How the harness finds a configuration's scene description and plain
reference (`run.py::config_modules`), with no render of the program:

  - every configuration in BENCHMARK.json resolves to `scenedesc.py` and
    `reference.py`, the very module objects the harness loads by name;
  - a configuration that names its own under "scene_description" and
    "reference" gets them in `make_ctx`, `reference_pixels` (so
    `check_film`), the two controls of `control.py` and `seed_check.py`;
  - an unknown name, or a module without what the harness calls, stops the
    run in `make_ctx`, before any call of the driver.

The test-only modules are written into each test's own temporary directory.
Run by hand, like its siblings: python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import numpy as np
import pytest

import run as harness

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
BASE = harness.load_json(harness.ROOT, "benchmark", "configs", "cornell-path.json")

SENTINEL_REFERENCE = '''
import numpy as np

CALLS = []
VALUE = 0.25


def make_renderer(desc, ray_block, dtype=None, intersect_dtype=None):
    CALLS.append({"desc": desc, "ray_block": ray_block, "dtype": dtype, "intersect_dtype": intersect_dtype})
    return lambda pix_xy, spp, seed: np.full((len(pix_xy), 3), VALUE)
'''

SENTINEL_DESCRIPTION = '''
WRITTEN = []


class Written(Exception):
    pass


def write_scene(desc, out_dir, stem):
    WRITTEN.append(desc)
    raise Written(stem)
'''


def write_module(directory, name, source):
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, name + ".py"), "w") as fh:
        fh.write(source)


def bench_for(tmp_path, config):
    """A benchmark of one cell, `t-cell`, on `config` written to a file."""
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    return {
        "run_seconds": 12,
        "configs": [{"name": "t-config", "file": str(path)}],
        "workloads": [{"name": "t-cell", "config": "t-config", "traffic": "frames", "chips": 1}],
    }


@pytest.fixture
def own_modules(tmp_path, monkeypatch):
    """The harness's lookup directories moved to `tmp_path`, holding a
    sentinel reference and a sentinel description -> (reference, description)."""
    for key, source in (("reference", SENTINEL_REFERENCE), ("scene_description", SENTINEL_DESCRIPTION)):
        kind, default, lacks = harness.NAMED_MODULES[key]
        directory = str(tmp_path / kind)
        write_module(directory, "sentinel", source)
        monkeypatch.setitem(harness.NAMED_MODULES, key, (directory, default, lacks))
    return (harness.load_module(str(tmp_path / "references"), "sentinel"),
            harness.load_module(str(tmp_path / "descriptions"), "sentinel"))


@pytest.fixture
def setup_calls(monkeypatch):
    """Every call of the frames driver's `setup`, recorded and not made."""
    calls = []
    monkeypatch.setattr(harness.load_module("drivers", "frames"), "setup", calls.append)
    return calls


@pytest.mark.parametrize("entry", BENCH["configs"], ids=[c["name"] for c in BENCH["configs"]])
def test_every_configuration_takes_the_default_modules(entry):
    reference, scenedesc = harness.load_module("", "reference"), harness.load_module("", "scenedesc")
    config = harness.load_json(harness.ROOT, entry["file"])
    assert "reference" not in config and "scene_description" not in config
    mods = harness.config_modules(config)
    assert mods["reference"] is reference and mods["scene_description"] is scenedesc
    cell = next(w["name"] for w in BENCH["workloads"] if w["config"] == entry["name"])
    ctx, _, _ = harness.make_ctx(BENCH, cell, 5, 0.0, False, "rehearsal")
    assert ctx["reference"] is reference and ctx["write_scene"] is scenedesc.write_scene


def test_named_modules_reach_make_ctx_and_check_film(tmp_path, own_modules):
    reference, description = own_modules
    config = dict(BASE, reference="sentinel", scene_description="sentinel")
    ctx, _, config = harness.make_ctx(bench_for(tmp_path, config), "t-cell", 5, 0.0, False, "rehearsal")
    assert ctx["reference"] is reference and ctx["write_scene"] is description.write_scene
    ctx["desc"] = {"the": "description"}

    class Driver:
        def __init__(self, value):
            self.value = value

        def film(self, ctx):
            shape = (int(config["yresolution"]), int(config["xresolution"]))
            return np.full(shape + (3,), self.value), np.full(shape, float(config["pixelsamples"]))

        def release(self, ctx):
            pass

    correct, rows = harness.check_film(ctx, Driver(reference.VALUE), config)
    assert correct and rows["mean_gap"]["value"] == 0.0, rows
    assert reference.CALLS[-1] == {"desc": ctx["desc"], "ray_block": int(config["check"]["ray_block"]),
                                   "dtype": None, "intersect_dtype": None}
    correct, rows = harness.check_film(ctx, Driver(2 * reference.VALUE), config)
    assert not correct and rows["mean_gap"]["value"] == 1.0, rows


def test_named_reference_serves_both_controls(tmp_path, own_modules):
    import control

    reference, _ = own_modules
    config = dict(BASE, reference="sentinel", scene_description="sentinel")
    ctx, _, config = harness.make_ctx(bench_for(tmp_path, config), "t-cell", 5, 0.0, False, "rehearsal")
    ctx["desc"] = {"the": "description"}
    assert set(control.CONTROLS.values()) == {"dtype", "intersect_dtype"}
    for kw in control.CONTROLS.values():
        pix, px = harness.reference_pixels(ctx, config, key_offset=1, **{kw: "lower"})
        assert px.shape == (len(pix), 3)
        assert reference.CALLS[-1][kw] == "lower" and reference.CALLS[-1]["desc"] is ctx["desc"]


def test_named_description_reaches_seed_check(tmp_path, own_modules):
    import seed_check

    _, description = own_modules
    config = dict(BASE, reference="sentinel", scene_description="sentinel")
    with pytest.raises(description.Written):
        seed_check.signature(config, 7, str(tmp_path / "work"))
    assert description.WRITTEN[-1]["meshes"]  # the scene writer's description, handed on


@pytest.mark.parametrize("key, name", [
    ("reference", "no_such_reference"),
    ("scene_description", "no_such_description"),
    ("reference", "../reference"),  # the file exists, but outside the directory
])
def test_unknown_name_stops_before_the_driver(tmp_path, monkeypatch, setup_calls, key, name):
    bench = bench_for(tmp_path, dict(BASE, **{key: name}))
    load_json = harness.load_json
    monkeypatch.setattr(harness, "load_json",
                        lambda *parts: bench if parts[-1] == "BENCHMARK.json" else load_json(*parts))
    with pytest.raises(SystemExit) as e:
        harness.run_cell(["--workload", "t-cell", "--seconds", "0", "--preset", "rehearsal"])
    kind = harness.NAMED_MODULES[key][0]
    assert f'configuration key "{key}"' in str(e.value)
    assert harness.module_path(kind, name) in str(e.value)
    assert setup_calls == []


@pytest.mark.parametrize("key, source, lacks", [
    ("reference", "def make_renderer(desc, ray_block, dtype=None):\n    pass\n", "intersect_dtype"),
    ("reference", "def make_renderer(desc, ray_block, intersect_dtype=None):\n    pass\n", "dtype"),
    ("reference", "def make_renderer(desc, ray_block, dtype=None, *, intersect_dtype):\n    pass\n",
     "intersect_dtype"),
    ("reference", "def render(desc):\n    pass\n", "make_renderer"),
    ("scene_description", "def write(desc, out_dir, stem):\n    pass\n", "write_scene"),
])
def test_module_without_what_the_harness_calls_is_refused_when_loaded(
        tmp_path, monkeypatch, setup_calls, key, source, lacks):
    kind, default, check = harness.NAMED_MODULES[key]
    directory = str(tmp_path / kind)
    write_module(directory, "lacking", source)
    monkeypatch.setitem(harness.NAMED_MODULES, key, (directory, default, check))
    config = dict(BASE, **{key: "lacking"})
    with pytest.raises(SystemExit) as e:
        harness.config_modules(config)
    assert f'configuration key "{key}"' in str(e.value) and lacks in str(e.value).split(" lacks ")[-1]
    with pytest.raises(SystemExit):
        harness.make_ctx(bench_for(tmp_path, config), "t-cell", 5, 0.0, False, "rehearsal")
    assert setup_calls == []
