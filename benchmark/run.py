#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout, on the machine that holds the cell's chips.
Everything that belongs to one cell is data that this harness finds by the
names in BENCHMARK.json:

    configs/<config>.json     the configuration as it is run (BENCHMARK.json's `file`)
    scenes/<writer>.py        build(config, seed) -> scene description
    traffic/<traffic>.json    the traffic mix; names its driver
    drivers/<driver>.py       setup / window / film / release
    metrics/<metric>.py       read(ctx) -> number, or None where there is nothing to read

so a later PR adds a cell, a configuration, a mix, a driver or a metric with
new files and BENCHMARK.json entries, and edits nothing here.

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
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """`<kind>/<name>.py` of the benchmark's directory as a module, loaded once."""
    path = os.path.join(HERE, kind, name + ".py") if kind else os.path.join(HERE, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
    """The plain reference on the pixels drawn from the seed ->
    (pix_xy, (N,3) radiance). `lower` (`dtype=` or `intersect_dtype=`
    bfloat16) makes it a control."""
    compare = load_module("", "compare")
    reference = load_module("", "reference")
    check = config["check"]
    pix = compare.sample_pixels(
        int(config["xresolution"]), int(config["yresolution"]), int(check["pixels"]), ctx["seed"]
    )
    render = reference.make_renderer(ctx["desc"], ray_block=int(check["ray_block"]), **lower)
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
        "write_scene": load_module("", "scenedesc").write_scene,
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
