#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout, on the machine that holds the cell's chips.
Everything that belongs to one cell is data that this harness finds by the
names in BENCHMARK.json:

    configs/<config>.json     the configuration as it is run (BENCHMARK.json's `file`)
    scenes/<writer>.py        build(config, seed) -> scene description
    descriptions/<name>.py    write_scene(desc, out_dir, stem) -> path of the .pbrt
    references/<name>.py      make_renderer(desc, ray_block, dtype=, intersect_dtype=)
    traffic/<traffic>.json    the traffic mix; names its driver
    drivers/<driver>.py       setup / window / film / release
    metrics/<metric>.py       read(ctx) -> number, or None where there is nothing to read

so a later PR adds a cell, a configuration, a mix, a driver or a metric with
new files and BENCHMARK.json entries, and edits nothing here.

A configuration's file names its scene writer under "scene_writer", and may
name its own scene description under "scene_description" and its own plain
reference under "reference". Where either key is absent the configuration
takes `scenedesc.py` and `reference.py` of this directory (matte surfaces,
diffuse area lights, point lights). `config_modules` resolves both, once, for
every caller (`make_ctx`, so `check_film` and `control.py`; `seed_check.py`),
and refuses before any set-up a name with no file, and a reference without
the two keywords the controls pass. What such a module must do:

  - a description writes what a user gives the program, a `.pbrt` file and
    its meshes, from the description alone; the same description writes the
    same bytes;
  - a reference imports nothing of `tpu_pbrt` and reads the description
    alone; it computes in float32 element-wise, or under
    `jax.default_matmul_precision("highest")` where it multiplies matrices;
    `make_renderer(...)` returns `render(pix_xy, spp, seed) -> (N, 3)` mean
    radiance, and `dtype=` / `intersect_dtype=` lower the whole of it, or its
    ray-triangle test alone, as `reference.py` defines them. It may import
    the module-level helpers of `reference.py` (`flatten_scene`, `_camera`)
    or copy what it needs.

The last line of standard output is the result, one JSON object. With
`--trace 0` its metrics are the cell's end-to-end metrics, with `--trace 1`
its per-layer metrics, read from counters and from a `jax.profiler` trace of
one whole timed frame. A run that finds no accelerator, or fewer chips than
the cell asks for, prints no result and exits non-zero.

`--preset rehearsal` is for the sandbox without a chip: that preset of the
configuration's file (tiny sizes), the CPU allowed, every code path driven,
NO result line, exit code 3: a number from a CPU never stands under a
device metric's name.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def module_path(kind: str, name: str) -> str:
    return os.path.join(HERE, kind, name + ".py")


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """`<kind>/<name>.py` of the benchmark's directory as a module, loaded once."""
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}".replace("-", "_"), module_path(kind, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lacks_description(mod) -> str:
    """What a scene description lacks of what the harness calls, or ""."""
    return "" if callable(getattr(mod, "write_scene", None)) else "write_scene(desc, out_dir, stem)"


def _lacks_reference(mod) -> str:
    """What a plain reference lacks of what `reference_pixels` and the two
    controls of `control.py` pass, or ""."""
    make = getattr(mod, "make_renderer", None)
    if not callable(make):
        return "make_renderer(desc, ray_block, dtype=..., intersect_dtype=...)"
    params = inspect.signature(make).parameters
    keyword = (inspect.Parameter.POSITIONAL_OR_KEYWORD, inspect.Parameter.KEYWORD_ONLY)
    missing = [k for k in ("desc", "ray_block", "dtype", "intersect_dtype")
               if k not in params or params[k].kind not in keyword
               or (k.endswith("dtype") and params[k].default is inspect.Parameter.empty)]
    return f"make_renderer's keyword(s) {', '.join(missing)}" if missing else ""


#: a configuration's optional keys that name a module of its own -> (the
#: directory the name is looked up in, the module taken where the key is
#: absent, what a module lacks of what the harness calls)
NAMED_MODULES = {
    "scene_description": ("descriptions", "scenedesc", _lacks_description),
    "reference": ("references", "reference", _lacks_reference),
}


def config_modules(config: dict) -> dict:
    """{"scene_description": module, "reference": module} of a configuration:
    those its file names, or NAMED_MODULES' defaults. A name with no file,
    or a module that lacks what the harness calls, stops the run here,
    before any set-up."""
    out = {}
    for key, (kind, default, lacks) in NAMED_MODULES.items():
        name = config.get(key)
        if name is None:
            mod = load_module("", default)
        else:
            path = module_path(kind, str(name))
            if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_-]*", str(name)) or not os.path.isfile(path):
                raise SystemExit(f'configuration key "{key}": {name!r} names no module: looked for {path}')
            mod = load_module(kind, name)
        missing = lacks(mod)
        if missing:
            raise SystemExit(f'configuration key "{key}": {mod.__file__} lacks {missing}')
        out[key] = mod
    return out


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def metrics_for(bench: dict, group: str, cell: str):
    return [m for m in bench[group] if "workloads" not in m or cell in m["workloads"]]


def reference_pixels(ctx, config, key_offset: int = 0, **lower):
    """The configuration's plain reference (`ctx["reference"]`) on the pixels
    drawn from the seed -> (pix_xy, (N,3) radiance). `lower` (`dtype=` or
    `intersect_dtype=` bfloat16) makes it a control."""
    compare = load_module("", "compare")
    check = config["check"]
    pix = compare.sample_pixels(
        int(config["xresolution"]), int(config["yresolution"]), int(check["pixels"]), ctx["seed"]
    )
    render = ctx["reference"].make_renderer(ctx["desc"], ray_block=int(check["ray_block"]), **lower)
    return pix, render(pix, int(check["ref_spp"]), ctx["seed"] + key_offset)


def film_gaps(config, pix, prog_px, ref_px) -> dict:
    import numpy as np

    compare = load_module("", "compare")
    prog_px = np.nan_to_num(np.asarray(prog_px), nan=0.0, posinf=0.0, neginf=0.0)
    return compare.gaps(
        prog_px, ref_px, pix, int(config["xresolution"]), int(config["yresolution"]),
        int(config["check"]["tiles"]),
    )


def check_film(ctx, driver, config) -> tuple:
    """Compare the timed film with the plain reference -> (correct, rows).
    Runs after the window has closed, the memory peak has been read and the
    program's state is freed."""
    compare = load_module("", "compare")
    got = driver.film(ctx)
    driver.release(ctx)
    if got is None:
        return False, {"film": {"value": None, "limit": "a finished frame"}}
    image, weight = got
    numbers = compare.film_numbers(image, weight, int(config["pixelsamples"]))
    t = time.monotonic()
    pix, ref_px = reference_pixels(ctx, config)
    ctx["reference_s"] = time.monotonic() - t
    numbers.update(film_gaps(config, pix, image[pix[:, 1], pix[:, 0]], ref_px))
    return compare.verdict(numbers, config["check"]["limits"])


def make_ctx(bench: dict, workload: str, seed: int, seconds, trace: bool, preset: str = ""):
    """-> (ctx, driver, config) for one run of one cell."""
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, entry["file"])
    if preset:
        config = merge(config, config["presets"][preset])
    named = config_modules(config)
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)  # the system under test, from this checkout
    work_dir = os.path.join(ROOT, ".bench_work", cell["name"])
    shutil.rmtree(work_dir, ignore_errors=True)
    ctx = {
        "t_start": T_START, "cell": cell, "config": config, "traffic": traffic,
        "seed": int(seed), "trace": bool(trace), "rehearse": bool(preset), "work_dir": work_dir,
        "seconds": float(bench["run_seconds"]) if seconds is None else float(seconds),
        "scene_writer": load_module("scenes", config["scene_writer"]),
        "write_scene": named["scene_description"].write_scene,
        "reference": named["reference"],
    }
    return ctx, load_module("drivers", traffic["driver"]), config


def run_cell(argv=None):
    """One run -> (exit code, result or None); `main` prints."""
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--preset", default="", help="a preset of the configuration's file; no result line")
    ap.add_argument("--keep-trace", default="", metavar="DIR",
                    help="copy the traced run's .xplane.pb into DIR (inside the checkout)")
    args = ap.parse_args(argv)

    preset = args.preset
    bench = load_json(ROOT, "BENCHMARK.json")
    if args.workload not in {w["name"] for w in bench["workloads"]}:
        say(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2, None
    ctx, driver, config = make_ctx(bench, args.workload, args.seed, args.seconds, args.trace, preset)
    cell, work_dir = ctx["cell"], ctx["work_dir"]
    try:
        driver.setup(ctx)
        if not preset:
            peaks = load_json(HERE, "peaks.json")
            if ctx["device"]["kind"] not in peaks:
                say(f"device kind {ctx['device']['kind']!r} is not in benchmark/peaks.json")
                return 1, None
        driver.window(ctx)
        if ctx.get("xplane_path"):
            t = time.monotonic()
            ctx["trace"] = load_module("", "tracereduce").reduce_trace(ctx["xplane_path"])
            ctx["trace_reduce_s"] = time.monotonic() - t
            ctx["xplane_bytes"] = os.path.getsize(ctx["xplane_path"])
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(ctx["xplane_path"], args.keep_trace)
        elif args.trace:
            ctx["trace"] = None
        correct, rows = check_film(ctx, driver, config)
        correct = bool(correct and ctx["failed"] == 0)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, group, cell["name"]):
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(ctx["device"], memory_peak_bytes=ctx["memory_peak_bytes"])
    result = {
        "correct": correct, "attempted": ctx["attempted"], "failed": ctx["failed"],
        "metrics": metrics, "device": device,
    }
    tr = ctx.get("trace") if args.trace else None
    if tr:
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    result["notes"] = {
        k: ctx[k] for k in ("setup_s", "scene_compile_s", "warmup_s", "window_s", "frame_seconds", "reference_s",
                            "trace_reduce_s", "xplane_bytes", "compiles_before", "compiles_after")
        if k in ctx
    }
    result["notes"]["errors"] = [f["error"] for f in ctx["frames"] if "error" in f]
    result["compared"] = rows
    if preset:
        return 3, result
    if args.trace and not tr:
        say("the traced run gave no device op or no frame annotation")
        return 1, None
    return 0, result


def main(argv=None) -> int:
    code, result = run_cell(argv)
    if code == 3:
        say(json.dumps(result, indent=1, default=str))
        say("preset run: no result (a CPU run, or a size that is not the cell's, measures nothing)")
    if code != 0:
        return code
    for name, row in result["compared"].items():
        say(f"compared {name}: value={row['value']} limit={row['limit']}")
    say(f"correct={result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
