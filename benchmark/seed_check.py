#!/usr/bin/env python3
"""Off-chip check that `--seed` changes only what the device READS.

For every configuration in BENCHMARK.json and a dozen seeds (large ones
among them): the shapes and dtypes of `scene.dev`'s leaves, and the text of
the lowered chunk program, must be the same as for the first seed. Another
shape or another baked constant is another compiled program, 55-90 s each
on the chip, in every run of every later check. Exits non-zero otherwise.

    JAX_PLATFORMS=cpu python3 benchmark/seed_check.py [--seeds N] [config ...]

Run it here, without the chip: nothing executes, the program is only traced
and lowered (at the CPU's chunk size; a constant that a seed changes shows
at any chunk size).
"""

from __future__ import annotations

import argparse
import hashlib
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from run import config_modules, load_json, load_module  # noqa: E402

SEEDS = [0, 1, 2, 3, 7, 11, 12345, 99991, 2**31 - 1, 2**31 + 11, 2147483777, 1234567891]


def signature(config: dict, seed: int, work: str):
    import jax

    from tpu_pbrt.scene.api import Options, compile_file

    desc = load_module("scenes", config["scene_writer"]).build(config, seed)
    path = config_modules(config)["scene_description"].write_scene(desc, work, "scene")
    scene, integ = compile_file(path, Options(quiet=True))
    leaves = jax.tree_util.tree_flatten_with_path(scene.dev)[0]
    shapes = [(jax.tree_util.keystr(k), tuple(getattr(v, "shape", ())), str(getattr(v, "dtype", type(v))))
              for k, v in leaves]
    plan = integ.prepare_chunks(scene)
    text = plan.jfn.lower(scene.film.init_state(), scene.dev, *plan.starts[0]).as_text()
    return shapes, hashlib.sha256(text.encode()).hexdigest(), len(text)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("configs", nargs="*")
    ap.add_argument("--seeds", type=int, default=len(SEEDS))
    args = ap.parse_args()
    bench = load_json(ROOT, "BENCHMARK.json")
    work = os.path.join(ROOT, ".bench_work", "seed_check")
    bad = 0
    try:
        for entry in bench["configs"]:
            if args.configs and entry["name"] not in args.configs:
                continue
            config = load_json(ROOT, entry["file"])
            first = None
            for seed in SEEDS[: args.seeds]:
                got = signature(config, seed, work)
                first = first or got
                same_shapes, same_text = got[0] == first[0], got[1] == first[1]
                print(f"{entry['name']} seed={seed} leaves={len(got[0])} program={got[1][:12]} "
                      f"({got[2]} chars) shapes_equal={same_shapes} program_equal={same_text}", flush=True)
                if not same_shapes:
                    for a, b in zip(first[0], got[0]):
                        if a != b:
                            print(f"   {a} != {b}")
                bad += (not same_shapes) + (not same_text)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print("seed check:", "FAILED" if bad else "passed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
