#!/usr/bin/env python
"""Treelet/leaf re-sweep under pool waves (ROADMAP carried item).

STREAM_LEAF_TRIS (512), TPU_PBRT_SLAB (2^17) and the segmented deposit
window (pool/4) were tuned on 1M-ray fixed-batch camera waves; the regen
pool's smaller, denser waves (chunk/4 slots, camera+shadow 2R trace
batches) plausibly want a different leaf/slab/deposit balance. This
harness grids the three knobs over the POOL drain shape and emits a JSON
table, one row per configuration:

    python tools/sweep_leaf.py --out sweep.json
    python tools/sweep_leaf.py --leaf 256,512 --slab 65536,131072 \
        --deposit 0,-1 --chunk 262144 --quick

Each cell runs in a SUBPROCESS: TPU_PBRT_* knobs are snapshotted at
import (config.py contract) and STREAM_LEAF_TRIS changes the compiled
scene, so a fresh interpreter per cell is the only honest measurement.
The child renders a killeroo-like scene through the regen pool
(pool = chunk/4, the production heuristic) and reports Mray/s, wave
occupancy and wave count.

Defaults policy: the committed defaults encode LIVE v5e measurements
(accel/stream.py's STREAM_LEAF_TRIS sweep note). A CPU sweep ranks
configurations by a cost model that does not transfer to the MXU, so
this tool REFUSES to recommend moving defaults unless the measurement
ran on a TPU backend — rows carry `backend` so the reader can tell. Run
it on the next live capture; if the argmax moves, update
STREAM_LEAF_TRIS / TPU_PBRT_SLAB defaults and note the capture id.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import subprocess
import sys
import time

_CHILD = r"""
import json, os, sys, time
res = int(os.environ["SWEEP_RES"]); spp = int(os.environ["SWEEP_SPP"])
chunk = int(os.environ["SWEEP_CHUNK"])
from tpu_pbrt.scenes import compile_api, make_killeroo_like
api = make_killeroo_like(res=res, spp=spp, integrator="path", maxdepth=5,
                         n_theta=24, n_phi=48)
scene, integ = compile_api(api)
import jax
# warmup populates the jit cache; the measured leg re-renders the same
# shapes so the row is compile-free
r0 = integ.render(scene)
t0 = time.time()
r1 = integ.render(scene)
jax.block_until_ready(r1.film_state)
secs = time.time() - t0
print(json.dumps({
    "mray_per_sec": r1.rays_traced / max(secs, 1e-9) / 1e6,
    "rays": int(r1.rays_traced),
    "seconds": secs,
    "mean_wave_occupancy": r1.stats.get("mean_wave_occupancy"),
    "n_waves": r1.stats.get("n_waves"),
    "pool": r1.stats.get("pool"),
    "backend": jax.default_backend(),
}))
"""


def run_cell(leaf, slab, deposit, args):
    env = dict(os.environ)
    env.update(
        {
            "TPU_PBRT_LEAF_TRIS": str(leaf),
            "TPU_PBRT_SLAB": str(slab),
            "TPU_PBRT_DEPOSIT_SEG": str(deposit),
            "TPU_PBRT_CHUNK": str(args.chunk),
            "SWEEP_RES": str(args.res),
            "SWEEP_SPP": str(args.spp),
            "SWEEP_CHUNK": str(args.chunk),
        }
    )
    t0 = time.time()
    try:
        out = subprocess.run(
            [sys.executable, "-c", _CHILD],
            env=env, capture_output=True, text=True,
            timeout=args.timeout,
        )
        line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
        row = json.loads(line) if line.startswith("{") else {
            "error": (out.stderr or "no output")[-800:],
        }
    except subprocess.TimeoutExpired:
        row = {"error": f"timeout after {args.timeout}s"}
    row.update(
        {
            "leaf_tris": leaf,
            "slab": slab,
            "deposit_seg": deposit,
            "chunk": args.chunk,
            "wall_seconds": round(time.time() - t0, 1),
        }
    )
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tools/sweep_leaf.py")
    ap.add_argument("--leaf", default="256,512,1024",
                    help="comma list of STREAM_LEAF_TRIS values")
    ap.add_argument("--slab", default="32768,65536,131072",
                    help="comma list of TPU_PBRT_SLAB caps")
    ap.add_argument("--deposit", default="0,-1",
                    help="comma list of TPU_PBRT_DEPOSIT_SEG windows "
                         "(0 = auto pool/4, -1 = full width)")
    ap.add_argument("--chunk", type=int, default=1 << 18,
                    help="camera rays per dispatch; the pool drains "
                         "chunk/4 slots — the swept wave shape")
    ap.add_argument("--res", type=int, default=256)
    ap.add_argument("--spp", type=int, default=4)
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--quick", action="store_true",
                    help="64x64 spp2 cells (smoke of the harness itself)")
    ap.add_argument("--out", default=None, help="write the JSON table here")
    args = ap.parse_args(argv)
    if args.quick:
        args.res, args.spp = 64, 2
        args.chunk = min(args.chunk, 1 << 14)

    grid = list(
        itertools.product(
            [int(x) for x in args.leaf.split(",") if x],
            [int(x) for x in args.slab.split(",") if x],
            [int(x) for x in args.deposit.split(",") if x != ""],
        )
    )
    rows = []
    for i, (leaf, slab, dep) in enumerate(grid):
        row = run_cell(leaf, slab, dep, args)
        rows.append(row)
        v = row.get("mray_per_sec")
        print(
            f"[{i + 1}/{len(grid)}] leaf={leaf} slab={slab} dep={dep}: "
            + (f"{v:.3f} Mray/s occ={row.get('mean_wave_occupancy')}"
               if v is not None else f"ERROR {row.get('error', '')[:120]}"),
            flush=True,
        )

    ok = [r for r in rows if "mray_per_sec" in r]
    best = max(ok, key=lambda r: r["mray_per_sec"]) if ok else None
    on_tpu = bool(ok) and all(r.get("backend") != "cpu" for r in ok)
    table = {
        "sweep": {
            "scene": f"killeroo-like res={args.res} spp={args.spp}",
            "chunk": args.chunk,
            "pool": args.chunk // 4,
            "rows": rows,
            "best": best,
            "defaults_recommendation": (
                None
                if not best
                else (
                    {
                        "leaf_tris": best["leaf_tris"],
                        "slab": best["slab"],
                        "deposit_seg": best["deposit_seg"],
                    }
                    if on_tpu
                    else "CPU sweep — ranking does not transfer to the "
                         "MXU; re-run on a live TPU before moving the "
                         "committed defaults"
                )
            ),
        }
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(table, f, indent=2)
        print(f"wrote {args.out}")
    else:
        print(json.dumps(table))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
