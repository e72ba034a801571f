"""What EXPAND's primitives cost on the attached chip, at the pool wave's
own sizes (a slab of 131,072 pairs; `accel/stream.py`'s header and
`_PACK_ROWS` quote these readings, PERF.md section 6 keeps them):

  sort   jax.lax.sort of two i32 arrays on one key, EXPAND's key mix (the
         hit-children histogram of a killeroo-class top tree: 1.4 hit
         children a pair), over all 8 S tested children, and packed down
         to 4 S and 3 S rows as `_pack_children` leaves them; each stable
         (XLA:TPU sorts an iota along) and not
  take   jnp.take(table (8, 2^19), idx (131072,), axis=1) with random,
         sorted-unique, sorted-with-runs and ray-grouped indices, an
         iota, one row for every index, rows far apart, and a slab a
         quarter full whose empty lanes fetch one row or rows far apart
  pack   the back half of an EXPAND from the slab test's answer to the
         sorted candidates: the 8 S sort as it was, and pack + sort at 4
         and 3 rows

    python tools/expand_probe.py            # fails without a TPU

Each figure is the wall clock of a jitted loop of REPS calls whose input
hangs on the loop's carry (so that nothing is hoisted), over REPS.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from tpu_pbrt.accel.stream import _I32_MAX, _pack_children  # noqa: E402

S = 131072
R = 1 << 19
TB = 10
REPS = 40
#: hit children of a (ray, node) pair, % of pairs (ISSUE 36's CPU walk of
#: the killeroo-class top tree, camera rays; 7-8 folded into 6)
HIST = np.array([18.5, 40.5, 28.4, 9.6, 2.56, 0.32, 0.12])


def timed(step, *args):
    """ms a call of step(*args, zero): zero is an i32 0 the compiler
    cannot see through, for the step to add to its input."""

    @jax.jit
    def loop(*a):
        def body(_, c):
            out = step(*a, jnp.minimum(c, 0))
            # the whole of it is read, so that none of it is dead code
            bits = jax.lax.bitcast_convert_type(out, jnp.int32)
            return jnp.maximum(c, jnp.bitwise_xor.reduce(bits, axis=None) & 1)

        return jax.lax.fori_loop(0, REPS, body, jnp.int32(0))

    loop(*args).block_until_ready()
    best = np.inf
    for _ in range(3):
        t0 = time.perf_counter()
        loop(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 1e3 * best / REPS


def slab(rng):
    """One slab as EXPAND sees it after the slab test: (hit8, key8, code8,
    key_in, node, resume), pairs grouped by ray as a popped slab is."""
    rid = np.sort(rng.integers(0, R, S)).astype(np.int32)
    n_hit = rng.choice(len(HIST), S, p=HIST / HIST.sum())
    order = np.argsort(rng.random((8, S)), axis=0)
    hit8 = order < n_hit[None, :]
    leaf = rng.random((8, S)) < 0.36
    q = rng.integers(0, 1 << TB, (8, S))
    key8 = np.where(leaf, rid[None, :], (1 << 30) + (rid[None, :] << TB) + q)
    code8 = rng.integers(0, 116, (8, S))
    key_in = (1 << 30) + (rid << TB) + (1 << TB) - 1
    return tuple(jnp.asarray(x) for x in (
        hit8, key8.astype(np.int32), code8.astype(np.int32),
        key_in.astype(np.int32), np.zeros(S, np.int32), np.zeros(S, np.int32)))


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"expand_probe needs a TPU, found {dev.platform}")
    rng = np.random.default_rng(36)
    out = {"device_kind": dev.device_kind, "slab": S, "reps": REPS}
    hit8, key8, code8, key_in, node, resume = slab(rng)

    def sort2(key, code, z, is_stable=True):
        return jax.lax.sort(
            [key + z, code], num_keys=1, is_stable=is_stable)[1]

    def sort2_unstable(key, code, z):
        return sort2(key, code, z, is_stable=False)

    def dead8(z):
        return jnp.where(hit8, key8, _I32_MAX) + z, code8

    def wide(z):  # the back half as it was
        key, code = dead8(z)
        return sort2(key.reshape(-1), code.reshape(-1), 0)

    def pack(k_rows, z):
        key, code = dead8(z)
        return _pack_children(
            key, code, key_in, node, resume, jnp.int32(S), k_rows)

    def packed(k_rows):
        def step(z):
            key, code, _ = pack(k_rows, z)
            return sort2(key, code, 0)
        return step

    def pack_only(k_rows):
        def step(z):
            key, code, _ = pack(k_rows, z)
            return key + code
        return step

    key_w, code_w = (x.reshape(-1) for x in dead8(0))
    out["live_pct_8S"] = float(100 * jnp.mean(key_w != _I32_MAX))
    out["sort_ms"] = {"8S": timed(sort2, key_w, code_w),
                      "8S_unstable": timed(sort2_unstable, key_w, code_w)}
    for k_rows in (4, 3):
        key, code, back = pack(k_rows, 0)
        out[f"put_back_pct_{k_rows}"] = float(100 * jnp.mean(back))
        out["sort_ms"][f"{k_rows}S"] = timed(
            sort2, key, code)
        out["sort_ms"][f"{k_rows}S_unstable"] = timed(
            sort2_unstable, key, code)
    out["back_half_ms"] = {
        "8S": timed(wide), "4S": timed(packed(4)), "3S": timed(packed(3)),
        "pack4_alone": timed(pack_only(4)), "pack3_alone": timed(pack_only(3)),
    }

    table = jnp.asarray(rng.random((8, R), np.float32))
    runs = np.sort(rng.integers(0, R, S)).astype(np.int32)
    idx = {
        "random": rng.integers(0, R, S).astype(np.int32),
        "sorted_unique": np.sort(rng.choice(R, S, replace=False)).astype(np.int32),
        "sorted_runs": runs,
        # a popped slab: each ray's pairs together, the rays in no order
        "grouped_runs": rng.permutation(np.unique(runs)).astype(np.int32)[
            np.cumsum(np.concatenate([[0], np.diff(runs) != 0]))],
        "iota": np.arange(S, dtype=np.int32),
        # what a slab's empty lanes fetch: one row all of them, or rows
        # far apart
        "constant": np.full(S, R - 1, np.int32),
        "strided": (np.arange(S, dtype=np.int64) * 8191 % R).astype(np.int32),
    }
    grouped = idx["grouped_runs"]
    for name in ("constant", "strided"):  # a slab a quarter full
        idx["quarter_grouped_rest_" + name] = np.where(
            np.arange(S) < S // 4, grouped, idx[name])
    out["take_ns_per_index"] = {
        name: 1e6 * timed(
            lambda i, z: jnp.take(table, i + z, axis=1), jnp.asarray(i)) / S
        for name, i in idx.items()
    }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
