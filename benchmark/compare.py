"""The comparison that decides `correct`: the film of a timed frame against
the plain reference, number by number, each with a limit of its own.

    spp_gap    largest |filter weight - pixelsamples| over ALL pixels of the
               film (box filter: the weight is the count of samples the
               pixel received). Exact: limit 0.
    nonfinite  non-finite values in the developed image. Exact: limit 0.
    mean_gap   |mean of the program's film - mean of the reference| over the
               mean of the reference, on the sampled pixels, all channels.
    tile_gap   the same gap tile by tile (tiles x tiles over the image), the
               worst tile, each measured against the reference's mean in
               that tile or in the median tile, whichever is larger.

The sampled pixels are drawn from the seed. The limits are in the
configuration's file (`check.limits`), set as PERF.md section 2 records.
"""

from __future__ import annotations

import numpy as np


def sample_pixels(xres: int, yres: int, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0x5EED])
    flat = rng.choice(xres * yres, size=min(n, xres * yres), replace=False)
    flat.sort()
    return np.stack([flat % xres, flat // xres], axis=-1).astype(np.int32)


def gaps(prog_px, ref_px, pix_xy, xres: int, yres: int, tiles: int) -> dict:
    """prog_px, ref_px: (N,3) radiance at the sampled pixels."""
    prog_px = np.asarray(prog_px, np.float64)
    ref_px = np.asarray(ref_px, np.float64)
    ref_mean = ref_px.mean()
    out = {"mean_gap": abs(prog_px.mean() - ref_mean) / max(ref_mean, 1e-30)}
    tid = (pix_xy[:, 1] * tiles // yres) * tiles + pix_xy[:, 0] * tiles // xres
    pm = np.asarray([prog_px[tid == t].mean() for t in range(tiles * tiles) if (tid == t).any()])
    rm = np.asarray([ref_px[tid == t].mean() for t in range(tiles * tiles) if (tid == t).any()])
    out["tile_gap"] = float(np.max(np.abs(pm - rm) / np.maximum(rm, np.median(rm))))
    return out


def film_numbers(image, weight, spp: int) -> dict:
    image = np.asarray(image)
    return {
        "spp_gap": float(np.max(np.abs(np.asarray(weight, np.float64) - spp))),
        "nonfinite": float(np.count_nonzero(~np.isfinite(image))),
    }


def verdict(numbers: dict, limits: dict):
    """-> (correct, {name: {"value", "limit"}}). A number without a limit in
    the configuration, or one that is not finite, is not correct."""
    rows, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        rows[name] = {"value": value, "limit": limit}
        if limit is None or not np.isfinite(value) or value > limit:
            ok = False
    for name in limits:
        if name not in numbers:
            rows[name] = {"value": None, "limit": limits[name]}
            ok = False
    return ok, rows
