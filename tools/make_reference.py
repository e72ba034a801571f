#!/usr/bin/env python
"""Generate the CPU reference image for the judged MSE metric.

The judged metric (BASELINE.json) is Mray/s AND per-pixel MSE vs a CPU
reference render. This script renders the killeroo-simple-class workload on
the CPU backend at high spp and caches the float32 image; bench.py loads
the cache and compares the accelerator render against it.

Run: python tools/make_reference.py   (env: MSE_RES, REF_SPP)
The cache is keyed by (res, spp) so stale files are never silently reused.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REF_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "refimg")


def reference_path(res: int, spp: int) -> str:
    return os.path.join(REF_DIR, f"killeroo_cpu_{res}x{res}_{spp}spp.npz")


def make_reference(res: int, spp: int, quiet: bool = False):
    """Render the reference on CPU and cache it. Returns (image, mray/s)."""
    import jax
    import numpy as np

    from tpu_pbrt.scenes import compile_api, make_killeroo_like

    if jax.devices()[0].platform != "cpu":
        raise RuntimeError(f"the reference is a CPU render, not {jax.devices()}")
    api = make_killeroo_like(res=res, spp=spp)
    scene, integ = compile_api(api)
    result = integ.render(scene)
    img = np.asarray(result.image, np.float32)
    os.makedirs(REF_DIR, exist_ok=True)
    np.savez_compressed(
        reference_path(res, spp),
        image=img,
        res=res,
        spp=spp,
        mray_per_sec=result.mray_per_sec,
        seconds=result.seconds,
    )
    if not quiet:
        print(
            f"reference {res}x{res}@{spp}spp: cpu {result.mray_per_sec:.3f} Mray/s, "
            f"{result.seconds:.1f}s -> {reference_path(res, spp)}"
        )
    return img, result.mray_per_sec


if __name__ == "__main__":
    # pin the CPU before jax initialises a backend. Only here: bench.py
    # imports reference_path from this module on its way to the chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    res = int(os.environ.get("MSE_RES", "128"))
    spp = int(os.environ.get("REF_SPP", "256"))
    make_reference(res, spp)
