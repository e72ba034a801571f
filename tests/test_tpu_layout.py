"""The stream tracer's EXPAND compiled for the TPU that is described, not
attached (one v5e chip), at the shapes of the crown-geometry cell: the pool's
2^19-ray wave over a top tree of 3,263 nodes, which takes the native child
fetch. XLA:TPU's layout assignment is free to leave that gather's result,
and the whole slab test after it, laid out with the 8 children minor, 8 of
128 lanes used; it did so when EXPAND's pack first read rows of the (8, S)
test and nothing reduced over it, and the cell's frame went from 12.5 to
19.2 s with every CPU test passing (PERF.md, PR 36). Nothing runs here: the
compiled module's text is read for the layouts it chose."""

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


def test_expand_keeps_the_slab_axis_minor(one_chip):
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.accel.stream import FUSED_WAVE_RAYS, _sizes, stream_traverse_stats
    from tpu_pbrt.accel.treelet import TreeletPack
    from tpu_pbrt.accel.wide import WideBVH

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n_nodes, n_treelets, rays = 3263, 10234, FUSED_WAVE_RAYS
    slab = _sizes(rays)[0]
    tp = TreeletPack(
        top=WideBVH(spec((n_nodes, 8, 3)), spec((n_nodes, 8, 3)),
                    spec((n_nodes, 8), jnp.int32)),
        featT=spec((n_treelets, 16, 2048)), center=spec((n_treelets, 3)),
        offset=spec((n_treelets,), jnp.int32), count=spec((n_treelets,), jnp.int32),
    )
    text = stream_traverse_stats.lower(
        tp, spec((rays, 3)), spec((rays, 3)), spec((rays,))).compile().as_text()
    # every array of EXPAND whose LAST axis is the slab's (the (8, S) and
    # (6, 8, S) tests; a gather's own result has the slab axis first)
    shape = re.compile(r"(?:f32|s32|u32|pred)\[((?:\d+,)+%d)\]\{([\d,]+):" % slab)
    seen, odd = 0, set()
    for line in text.split("\n"):
        if "stream/expand" not in line or " = " not in line:
            continue
        result = re.split(r" [a-z][\w\-]*\(", line.split(" = ", 1)[1], 1)[0]
        for dims, minor_to_major in shape.findall(result):
            seen += 1
            if int(minor_to_major.split(",")[0]) != dims.count(","):
                odd.add(f"[{dims}]{{{minor_to_major}}}")
    assert seen > 20, "EXPAND's arrays were not found in the compiled text"
    assert not odd, f"slab axis not minor in EXPAND: {sorted(odd)}"
