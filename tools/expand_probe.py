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
  fetch  the native child fetch of a top tree of N = 3,263 nodes (crown's),
         each with the slab test's own reading of its answer: (i) the
         (6, 8, N) box take and the (8, N) code take; (ii) one take of
         (N, 56) node rows and one transpose, EXPAND's `_fetch_children`;
         (iii) one take of the (56, N) lane-major table along its lanes,
         as the `rayE` take; the slab test alone on a fetched answer; and
         a row slice against a column slice at the `rayE` table's own
         size: (2^19, 8) rows taken and transposed, the (8, 2^19) take
         as it is

    python tools/expand_probe.py [sort take pack fetch]   # fails without a TPU

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
from tpu_pbrt.accel.stream import (  # noqa: E402
    _I32_MAX, _bits, _fetch_children, _node_rows, _pack_children, _unbits)
from tpu_pbrt.accel.wide import _EMPTY, slab_test_lane_major  # noqa: E402

S = 131072
R = 1 << 19
TB = 10
#: crown-geometry's top-tree nodes
N_NODES = 3263
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


def fetch(rng):
    """ms a round of each way to fetch a slab's child boxes (6, 8, S) and
    codes (8, S), each read by the slab test and reduced over the 8
    children as EXPAND reads them, and ns an index of a row slice against
    a column slice of the `rayE` table."""
    lo = rng.normal(0.0, 10.0, (6, 8, N_NODES)).astype(np.float32)
    lo[3:] = lo[:3] + rng.random((3, 8, N_NODES), np.float32)
    empty = rng.random((8, N_NODES)) < 0.2  # +-inf bounds, _EMPTY codes
    lo[:3, empty], lo[3:, empty] = np.inf, -np.inf
    codes = np.where(rng.random((8, N_NODES)) < 0.4,
                     -1 - rng.integers(0, 10234, (8, N_NODES)),
                     rng.integers(1, N_NODES, (8, N_NODES)))
    codes = np.where(empty, _EMPTY, codes).astype(np.int32)
    boxT, cidT = jnp.asarray(lo), jnp.asarray(codes)
    rows = _node_rows(boxT, cidT)  # (N, 56), as EXPAND fetches them
    lanes = rows.T
    node = jnp.asarray(rng.integers(0, N_NODES, S).astype(np.int32))
    ray6 = jnp.asarray(rng.normal(0.0, 1.0, (6, S)).astype(np.float32))

    def test(nb, cids):  # the slab test and a reduction over the children
        t0, t1 = [], []
        for a in range(3):
            lo_t, hi_t = slab_test_lane_major(
                nb[a], nb[a + 3], ray6[a][None, :], ray6[a + 3][None, :])
            t0.append(lo_t)
            t1.append(hi_t)
        tn = jnp.maximum(jnp.maximum(t0[0], t0[1]), jnp.maximum(t0[2], 0.0))
        tf = jnp.minimum(jnp.minimum(t1[0], t1[1]), t1[2])
        hit = (tn <= tf) & (cids != _EMPTY)
        return (jnp.min(jnp.where(hit, cids, _I32_MAX), axis=0)
                + jnp.sum(hit, axis=0, dtype=jnp.int32))

    def two_takes(i, z):
        i = i + z
        return test(jnp.take(boxT, i, axis=2), jnp.take(cidT, i, axis=1))

    def row_take(i, z):  # EXPAND's own fetch
        return test(*_fetch_children(rows, i + z, False))

    def lanes_fetch(i):
        r = jnp.take(lanes, i, axis=1)  # (56, S)
        return _unbits(r[:48]).reshape(6, 8, S), r[48:]

    def lane_take(i, z):
        return test(*lanes_fetch(i + z))

    nb0 = jnp.take(boxT, node, axis=2)
    cid0 = jnp.take(cidT, node, axis=1)
    for nb, cids in (_fetch_children(rows, node, False), lanes_fetch(node)):
        # the same answer as the two takes, bit for bit
        assert (_bits(nb) == _bits(nb0)).all() and (cids == cid0).all()
    out = {
        "nodes": N_NODES,
        "ms_per_round": {
            "i_two_takes": timed(two_takes, node),
            "ii_row_take_transpose": timed(row_take, node),
            "iii_lane_take": timed(lane_take, node),
            "slab_test_alone": timed(lambda nb, c, z: test(nb, c + z), nb0, cid0),
        },
    }
    table = jnp.asarray(rng.random((8, R), np.float32))
    tableT = table.T  # (R, 8)
    rid = jnp.asarray(rng.integers(0, R, S).astype(np.int32))
    out["ray_table_ns_per_index"] = {
        "column_8_sublanes": 1e6 * timed(
            lambda i, z: jnp.take(table, i + z, axis=1), rid) / S,
        "row_8_floats_transposed": 1e6 * timed(
            lambda i, z: jnp.take(tableT, i + z, axis=0).T, rid) / S,
    }
    return out


def main():
    sections = set(sys.argv[1:]) or {"sort", "take", "pack", "fetch"}
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

    if "sort" in sections:
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
    if "pack" in sections:
        out["back_half_ms"] = {
            "8S": timed(wide), "4S": timed(packed(4)), "3S": timed(packed(3)),
            "pack4_alone": timed(pack_only(4)),
            "pack3_alone": timed(pack_only(3)),
        }
    if "fetch" in sections:
        out["fetch"] = fetch(np.random.default_rng(42))
    if "take" in sections:
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
