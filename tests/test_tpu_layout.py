"""The stream tracer's EXPAND compiled for the TPU that is described, not
attached (one v5e chip), at the shapes of the crown-geometry cell: the pool's
2^19-ray wave over a top tree of 3,263 nodes, which takes the native child
fetch. XLA:TPU's layout assignment is free to leave that gather's result,
and the whole slab test after it, laid out with the 8 children minor, 8 of
128 lanes used; it did so when EXPAND's pack first read rows of the (8, S)
test and nothing reduced over it, and the cell's frame went from 12.5 to
19.2 s with every CPU test passing (PERF.md, PR 36). Nothing runs here: the
compiled module's text is read for the layouts it chose, and for what
EXPAND's takes write: a take of the (6, 8, N) child boxes wrote each pair's
(6, 8) into a whole (8, 128) tile, 512 MiB a round for 25 MB of boxes, and
a copy transposed it (PERF.md section 6, the node-row fetch)."""

import math
import re

import pytest


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def expand_text(one_chip):
    """The compiled text of `stream_traverse_stats` at crown's shapes, and
    the slab's width."""
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.accel.stream import FUSED_WAVE_RAYS, _sizes, stream_traverse_stats
    from tpu_pbrt.accel.treelet import TreeletPack
    from tpu_pbrt.accel.wide import WideBVH

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n_nodes, n_treelets, rays = 3263, 10234, FUSED_WAVE_RAYS
    tp = TreeletPack(
        top=WideBVH(spec((n_nodes, 8, 3)), spec((n_nodes, 8, 3)),
                    spec((n_nodes, 8), jnp.int32)),
        featT=spec((n_treelets, 16, 2048)), center=spec((n_treelets, 3)),
        offset=spec((n_treelets,), jnp.int32), count=spec((n_treelets,), jnp.int32),
    )
    text = stream_traverse_stats.lower(
        tp, spec((rays, 3)), spec((rays, 3)), spec((rays,))).compile().as_text()
    return text, _sizes(rays)[0]


def _expand_results(text):
    """(op_name, result text) of every instruction EXPAND's scope names."""
    for line in text.split("\n"):
        if "stream/expand" not in line or " = " not in line:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        result = re.split(r" [a-z][\w\-]*\(", line.split(" = ", 1)[1], 1)[0]
        yield (name.group(1) if name else ""), result


def test_expand_keeps_the_slab_axis_minor(expand_text):
    text, slab = expand_text
    # every array of EXPAND whose LAST axis is the slab's (the (8, S) and
    # (6, 8, S) tests; a gather's own result has the slab axis first)
    shape = re.compile(r"(?:f32|s32|u32|pred)\[((?:\d+,)+%d)\]\{([\d,]+):" % slab)
    seen, odd = 0, set()
    for _, result in _expand_results(text):
        for dims, minor_to_major in shape.findall(result):
            seen += 1
            if int(minor_to_major.split(",")[0]) != dims.count(","):
                odd.add(f"[{dims}]{{{minor_to_major}}}")
    assert seen > 20, "EXPAND's arrays were not found in the compiled text"
    assert not odd, f"slab axis not minor in EXPAND: {sorted(odd)}"


def test_expand_fetch_writes_no_padded_node_tiles(expand_text):
    """No EXPAND array keeps a node's (6, 8) boxes as its two minor dims
    (one (8, 128) tile a pair), and what each of EXPAND's takes writes,
    counted tile-padded, is at most 64 MiB a round: the `rayE` take's
    (S, 8) and the node rows' (S, 56) are each S rows of one tile's 128
    lanes."""
    text, _ = expand_text
    shape = re.compile(
        r"(f32|s32|u32|pred)\[([\d,]+)\]\{([\d,]+)(?::T\((\d+),(\d+)\))?")
    width = {"f32": 4, "s32": 4, "u32": 4, "pred": 1}
    node_minor, takes = set(), {}
    for name, result in _expand_results(text):
        for dtype, dims, minor_to_major, t0, t1 in shape.findall(result):
            dims = [int(x) for x in dims.split(",")]
            order = [int(x) for x in minor_to_major.split(",")]
            if len(dims) < 2:
                continue
            if (dims[order[1]], dims[order[0]]) == (6, 8):
                node_minor.add(result.split()[0])
            if name.endswith("stream/expand/jit(_take)/gather") and t0:
                dims[order[0]] = -(-dims[order[0]] // int(t1)) * int(t1)
                dims[order[1]] = -(-dims[order[1]] // int(t0)) * int(t0)
                takes[result.split()[0]] = width[dtype] * math.prod(dims)
    assert not node_minor, f"a node's (6, 8) minor in EXPAND: {sorted(node_minor)}"
    assert len(takes) >= 2, "EXPAND's takes were not found in the compiled text"
    big = {k: v for k, v in takes.items() if v > 64 << 20}
    assert not big, f"EXPAND's takes write more than 64 MiB a round: {big}"
