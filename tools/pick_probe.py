"""What the spatial light pick's reads cost on the attached chip, at the
pool wave's own size (2^18 lanes) and at `killeroo-manylight`'s table (512
voxels x 8,192 light rows): the prices `lights_dev.PIVOT_TABLE_BUDGET_BYTES`
is set from (PERF.md section 6 keeps them).

  take    take_columns of 15 rows from a lane-major (15, 512 x c) pivot
          table of 30 KB, 480 KB, 1.9 MB and 7.9 MB (c = 1, 16, 64, 256:
          levels 0, 1 and 2 of the search at 512 voxels are c = 1, 16,
          256), each lane at one of its voxel's c columns
  gather  one scalar gather from the flat 16.8 MB (512 x 8,192) CDF, each
          lane in its voxel's row
  search  the whole of `SpatialLightDistribution.sample_discrete_at` with
          0 (the parent's 13 binary steps), 1, 2 and 3 pivot levels

Lanes cluster by voxel as a wave's vertices do in a room: 90 % of them in
64 of the 512 voxels, the rest anywhere.

    python tools/pick_probe.py            # fails without a TPU

Each figure is the wall clock of a jitted loop of REPS calls whose input
hangs on the loop's carry (`expand_probe.timed`), over REPS.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from expand_probe import timed  # noqa: E402

from tpu_pbrt.core import lights_dev as ld  # noqa: E402
from tpu_pbrt.core.smalltab import take_columns  # noqa: E402

LANES = 1 << 18
SIDE = 8
VOXELS = SIDE**3
LIGHTS = 8192


def clustered_voxels(rng):
    busy = rng.choice(VOXELS, 64, replace=False)
    return np.where(rng.random(LANES) < 0.9, rng.choice(busy, LANES), rng.integers(0, VOXELS, LANES))


def table(rng):
    """A (V, L) CDF as the compiler builds it: a few lights carry a voxel."""
    imp = rng.uniform(0.0, 1.0, (VOXELS, LIGHTS)) ** 8 + 1e-6
    imp /= imp.sum(-1, keepdims=True)
    cdf = np.cumsum(imp, -1).astype(np.float32)
    cdf[:, -1] = 1.0
    return cdf


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"pick_probe needs a TPU, found {dev.platform}")
    rng = np.random.default_rng(38)
    voxel = clustered_voxels(rng)
    out = {"device_kind": dev.device_kind, "lanes": LANES, "voxels": VOXELS, "lights": LIGHTS,
           "reps": 40}

    takes = {}
    for per_voxel in (1, 16, 64, 256):
        piv = jnp.asarray(rng.random((ld.PIVOTS, VOXELS * per_voxel), np.float32))
        col = jnp.asarray((voxel * per_voxel + rng.integers(0, per_voxel, LANES)).astype(np.int32))
        ms = timed(lambda t, c, z: take_columns(t, c + z), piv, col)
        takes[f"{piv.nbytes}B"] = {"ms": ms, "ns_per_element": 1e6 * ms / (LANES * ld.PIVOTS)}
    out["take_15_rows"] = takes

    cdf = table(rng)
    flat = jnp.asarray(cdf.reshape(-1))
    at = jnp.asarray((voxel * LIGHTS + rng.integers(0, LIGHTS, LANES)).astype(np.int32))
    ms = timed(lambda t, i, z: t[i + z], flat, at)
    out["gather_flat"] = {"bytes": flat.nbytes, "ms": ms, "ns_per_element": 1e6 * ms / LANES}

    sd = ld.SpatialLightDistribution.build(
        cdf, np.full(LIGHTS, 1.0 / LIGHTS, np.float32), np.zeros(3), np.full(3, float(SIDE)), (SIDE,) * 3)
    out["plan"] = sd.plan
    centre = (np.stack(np.unravel_index(voxel, (SIDE,) * 3, order="F"), -1) + 0.5) / SIDE
    p = jnp.asarray(centre, jnp.float32)
    u = jnp.asarray(rng.random(LANES, np.float32))
    want = None
    search = {}
    for levels in range(4):
        tables = sd._replace(pivots=tuple(jnp.asarray(t) for t in ld.pivot_tables(cdf, levels))).tables()

        def step(tables, u, p, z):
            idx, pmf = sd._replace(**tables).sample_discrete_at(u + z.astype(jnp.float32), p)
            return idx.astype(jnp.float32) + pmf

        got = jax.jit(step)(tables, u, p, jnp.int32(0))
        want = got if want is None else want
        search[f"{levels}_levels"] = {
            "ms": timed(step, tables, u, p),
            "pivot_bytes": sum(int(t.nbytes) for t in tables["pivots"]),
            "same_as_0_levels": bool(jnp.array_equal(got, want)),
        }
    out["search"] = search
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
