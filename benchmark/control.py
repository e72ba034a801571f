#!/usr/bin/env python3
"""Readings for the limits of the comparison (steps 3 to 5 of "How `correct`
is decided"), on the chip, at the cell's own size, in one process:

    python3 benchmark/control.py --workload <cell> --seeds 12 --control-seeds 3 [--out FILE]

For each seed: the scene, ONE timed frame through the driver (the window's
own entry and programs), the configuration's float32 reference (`reference.py`,
or the one its file names: `run.py`'s `config_modules`) on the pixels drawn
from the seed, and the program's numbers against it (the lower readings). For the
first `--control-seeds` seeds also the CONTROLS, each put in the program's
place and compared with the float32 reference, with random numbers of its
own as a program's would be (the upper readings): the same reference
computed in bfloat16, and the reference with the ray-triangle test alone in
bfloat16. `--faults` also plants the faults a frame can have in the film
the program produced (a dispatch that leaves its state unchanged: a quarter
of the rows without samples; half of the samples left out, the mean taken
over the rest; the chips' exchange left out: one chip's quarter of the
samples; radiance altered where it is deposited: +10 %, +5 %, +3 %) and
reads the numbers again. Every reading goes through `compare.verdict` with
the configuration's limits, at the cell's own size, and says `correct`.

Prints one JSON line per reading; `--preset test` runs it here on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402


def plant(fault: str, image, weight):
    """The film as that fault would leave it -> (image, weight)."""
    import numpy as np

    image, weight = np.array(image), np.array(weight)
    if fault == "state_unchanged":  # one dispatch of four deposits nothing
        rows = image.shape[0] // 4
        image[:rows], weight[:rows] = 0.0, 0.0
    elif fault == "half_batch":  # half of the samples, the mean over the rest
        weight = weight / 2
    elif fault == "no_exchange":  # one chip's share of the samples
        weight = weight / 4
    elif fault.startswith("altered"):  # radiance altered where it is deposited
        image = image * (1.0 + int(fault[len("altered"):]) / 100)
    return image, weight


FAULTS = ("state_unchanged", "half_batch", "no_exchange", "altered10", "altered5", "altered3")
CONTROLS = {"bfloat16": "dtype", "intersect_bfloat16": "intersect_dtype"}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_000_000_011)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--preset", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import jax.numpy as jnp

    compare = harness.load_module("", "compare")
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    out = open(args.out, "a") if args.out else None

    def emit(what, seed, numbers, limits, **more):
        ok, _ = compare.verdict(numbers, {k: limits[k] for k in numbers})
        line = json.dumps({"workload": args.workload, "seed": seed, "what": what,
                           "correct": ok, **numbers, **more})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ctx, driver, config = harness.make_ctx(bench, args.workload, seed, 0.0, False, args.preset)
        limits = config["check"]["limits"]
        driver.setup(ctx)
        driver.window(ctx)  # seconds = 0: one whole frame
        image, weight = driver.film(ctx)
        driver.release(ctx)
        spp = int(config["pixelsamples"])
        t = time.monotonic()
        pix, ref_px = harness.reference_pixels(ctx, config)
        ref_s = time.monotonic() - t
        at = lambda im: im[pix[:, 1], pix[:, 0]]  # noqa: E731
        numbers = compare.film_numbers(image, weight, spp)
        numbers.update(harness.film_gaps(config, pix, at(image), ref_px))
        emit("program", seed, numbers, limits, frame_s=ctx["frames"][0].get("seconds"),
             warmup_s=ctx["warmup_s"], reference_s=ref_s,
             mean_ratio=float(at(image).mean() / ref_px.mean()))
        if i >= args.control_seeds:
            continue
        if args.faults:
            for fault in FAULTS:
                im, w = plant(fault, image, weight)
                n = compare.film_numbers(im, w, spp)
                n.update(harness.film_gaps(config, pix, at(im), ref_px))
                emit("fault:" + fault, seed, n, limits)
        for name, kw in CONTROLS.items():
            _, ctl_px = harness.reference_pixels(ctx, config, key_offset=1, **{kw: jnp.bfloat16})
            emit("control:" + name, seed, harness.film_gaps(config, pix, ctl_px, ref_px), limits,
                 mean_ratio=float(ctl_px.mean() / ref_px.mean()))
        _, ref2_px = harness.reference_pixels(ctx, config, key_offset=2)
        emit("reference_vs_reference", seed, harness.film_gaps(config, pix, ref2_px, ref_px), limits,
             mean_ratio=float(ref2_px.mean() / ref_px.mean()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
