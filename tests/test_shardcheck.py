"""shardcheck (ISSUE 3 tentpole): static replication analysis over
shard_map bodies — adversarial fixtures (a body returning an unreduced
per-device value MUST be flagged), the collective-in-varying-loop rule,
and the repo-level mirror that keeps the real mesh entry points
verified. The mesh renderers run with jax's own check_vma on; the
fixtures here pass check_vma=False so that programs jax would reject at
trace time reach shardcheck, which must flag them on its own."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

import tpu_pbrt.parallel.mesh as mesh_mod
from tpu_pbrt.analysis import shardcheck
from tpu_pbrt.parallel.mesh import TILE_AXIS, shard_map


def _mesh(n=2):
    return Mesh(np.array(jax.devices()[:n]), (TILE_AXIS,))


def _scan(fn, *args, entry="fixture"):
    jx = jax.make_jaxpr(fn)(*args)
    return shardcheck.scan_closed_jaxpr(jx, entry)


# ---------------------------------------------------------------------------
# adversarial fixtures
# ---------------------------------------------------------------------------


def test_unreduced_output_flagged():
    """ISSUE 3 satellite: a shard_map body that returns a per-device
    partial value through a P() (replicated) out_spec must be flagged."""
    m = _mesh()

    @partial(shard_map, mesh=m, in_specs=(P(TILE_AXIS),), out_specs=P(),
             check_vma=False)
    def bad(x):
        return jnp.sum(x)  # no psum: device 0's partial would win

    findings, n = _scan(bad, jnp.ones((8,), jnp.float32))
    assert n == 1
    assert any(f.rule == "SC-UNREDUCED" for f in findings)


def test_psum_reduced_output_clean():
    m = _mesh()

    @partial(shard_map, mesh=m, in_specs=(P(TILE_AXIS),), out_specs=P(),
             check_vma=False)
    def good(x):
        return jax.lax.psum(jnp.sum(x), TILE_AXIS)

    findings, n = _scan(good, jnp.ones((8,), jnp.float32))
    assert n == 1 and findings == []


def test_all_gather_counts_as_replicating():
    """The sppm photon-exchange shape: all_gather over the axis makes
    every device hold the full set — replicated."""
    m = _mesh()

    @partial(shard_map, mesh=m, in_specs=(P(TILE_AXIS),), out_specs=P(),
             check_vma=False)
    def good(x):
        return jnp.sum(jax.lax.all_gather(x, TILE_AXIS, tiled=True))

    findings, n = _scan(good, jnp.ones((8,), jnp.float32))
    assert n == 1 and findings == []


def test_axis_index_taints_output():
    m = _mesh()

    @partial(shard_map, mesh=m, in_specs=(P(),), out_specs=P(),
             check_vma=False)
    def bad(x):
        return x + jax.lax.axis_index(TILE_AXIS)  # device-varying

    findings, n = _scan(bad, jnp.ones((8,), jnp.float32))
    assert any(f.rule == "SC-UNREDUCED" for f in findings)


def test_varying_sharded_out_spec_is_fine():
    """A P(axis)-sharded output is ALLOWED to vary — only claimed-
    replicated outputs are checked."""
    m = _mesh()

    @partial(shard_map, mesh=m, in_specs=(P(TILE_AXIS),),
             out_specs=P(TILE_AXIS), check_vma=False)
    def fine(x):
        return x * 2.0

    findings, n = _scan(fine, jnp.ones((8,), jnp.float32))
    assert n == 1 and findings == []


def test_replication_flows_through_while_loop():
    """A fully replicated while loop stays replicated (no false
    positive on lockstep loops)."""
    m = _mesh()

    @partial(shard_map, mesh=m, in_specs=(P(),), out_specs=P(),
             check_vma=False)
    def fine(x):
        def body(c):
            i, v = c
            return i + 1, v * 2.0

        return jax.lax.while_loop(lambda c: c[0] < 4, body, (0, x))[1]

    findings, n = _scan(fine, jnp.ones((8,), jnp.float32))
    assert n == 1 and findings == []


def test_collective_inside_varying_trip_loop_flagged():
    """Per-device trip counts + a collective in the body = mismatched
    collective counts across the mesh (deadlock on real hardware). The
    drain-loop contract (no collectives inside the drain) is exactly
    what this rule locks in."""
    m = _mesh()

    @partial(shard_map, mesh=m, in_specs=(P(TILE_AXIS),), out_specs=P(),
             check_vma=False)
    def bad(x):
        def body(c):
            i, v = c
            return i + 1.0, v + jax.lax.psum(v, TILE_AXIS)

        # bound depends on the device's shard -> per-device trip count
        _, v = jax.lax.while_loop(
            lambda c: c[0] < x[0], body, (jnp.float32(0.0), jnp.sum(x))
        )
        return jax.lax.psum(v, TILE_AXIS)

    findings, n = _scan(bad, jnp.ones((8,), jnp.float32))
    assert any(f.rule == "SC-LOOP-COLLECTIVE" for f in findings)


# ---------------------------------------------------------------------------
# the repo gate (tier-1 mirror of the CLI acceptance criterion)
# ---------------------------------------------------------------------------


def test_repo_mesh_entry_points_clean():
    """The real mesh programs (pool + chunk renderers, sppm mesh
    iteration) all verify: every claimed-replicated output is reduced."""
    errors, warnings = shardcheck.run_shardcheck()
    assert errors == [], "\n".join(errors)


def test_deleting_film_psum_is_caught(monkeypatch):
    """ISSUE 3 acceptance: removing the psum from the mesh step makes
    the suite exit non-zero with an entry-point diagnostic."""

    def broken_pool_renderer(mesh, per_device_drain):
        @partial(
            mesh_mod.shard_map, mesh=mesh,
            in_specs=(P(), P(TILE_AXIS)), out_specs=(P(), P()),
            check_vma=False,
        )
        def step(dev, starts):
            contrib, aux = per_device_drain(dev, starts)
            # BUG under test: film psum deleted; aux still reduced
            aux = jax.tree.map(
                lambda x: jax.lax.psum(x, TILE_AXIS), aux
            )
            return contrib, aux

        return step

    monkeypatch.setattr(
        mesh_mod, "sharded_pool_renderer", broken_pool_renderer
    )
    errors, _ = shardcheck.run_shardcheck(
        {"sharded_pool_renderer": __import__(
            "tpu_pbrt.analysis.audit", fromlist=["mesh_step_jaxpr"]
        ).mesh_step_jaxpr}
    )
    assert errors and "SC-UNREDUCED" in errors[0], errors
