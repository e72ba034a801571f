"""Distribution layer: the tile scheduler over a TPU device mesh.

Capability match for the reference's distributed layer (SURVEY.md §2e/§3.4)
and for src/core/parallel.{h,cpp}:
- ParallelFor2D's tile decomposition -> the flat work-index space of a
  dispatch is cut into GRANULES of `work_granule` consecutive work items
  (the samples of a few pixels) and dealt round-robin to the mesh devices
  inside a shard_map: device i takes granules i, i + n_dev, i + 2 n_dev, ...
  (`work_item`; static round-robin tile assignment: the fork's
  master/worker tile protocol collapsed into SPMD). Every device so draws
  from every part of the dispatch's image region at 1/n_dev density, and
  the drains end together without any device telling another how far it
  is: the balance is in the assignment, which is arithmetic on a work
  index, so the drains still hold no collective.
- Worker->master FilmTile return + Film::MergeFilmTile -> a `psum` over the
  mesh axis: film accumulation is associative, so the distributed film
  merge is ONE ICI all-reduce per chunk (the north star's "distributed film
  merge becomes an ICI all-reduce into a sharded framebuffer").
- The thread pool / work queue / mutex / AtomicFloat machinery has no
  equivalent here because the SPMD program replaces it: races are designed
  out (SURVEY.md §5.2).
- Multi-host: the same shard_map spans hosts under jax.distributed; the
  host-side spp-chunk loop is the dynamic re-dispatch seam for
  straggler/failure handling (chunks are idempotent pure functions of
  (scene, work range), SURVEY.md §5.3).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from tpu_pbrt.obs import phases as ph

TILE_AXIS = "tiles"


def vary(tree):
    """Mark every leaf of a loop's INITIAL carry as varying over the
    tile axis when tracing inside the tile shard_map; the identity
    anywhere else (single-device renders, tests calling the tracers
    directly).

    jax's varying-manual-axes check types each value inside a shard_map
    body as replicated or device-varying, and a while_loop/scan carry
    must keep one type: a carry seeded from constants (`jnp.zeros`, an
    iota, `-1` hit ids) is replicated, the body mixes in this device's
    work slice and returns it varying, and the trace is rejected. Every
    loop reachable from a mesh step therefore seeds its carry through
    this helper. Leaves that are already varying pass through."""
    if TILE_AXIS not in jax.sharding.get_abstract_mesh().manual_axes:
        return tree

    def one(x):
        x = jnp.asarray(x)
        if TILE_AXIS in jax.typeof(x).vma:
            return x
        return jax.lax.pcast(x, TILE_AXIS, to="varying")

    return jax.tree.map(one, tree)


def maybe_init_distributed(options=None) -> bool:
    """Multi-host seam: bring up the JAX distributed runtime (DCN
    coordination; the multi-host analog of the fork's master/worker
    socket channel). Activates when the standard cluster-environment
    variables are present (JAX_COORDINATOR_ADDRESS / auto-detected TPU
    pod env) or options.multihost is set. Idempotent; returns whether the
    distributed runtime is live. After this, jax.devices() spans all
    hosts and the same shard_map program runs pod-wide."""
    from tpu_pbrt.config import coordinator_address

    want = bool(getattr(options, "multihost", False)) or bool(
        coordinator_address()
    )
    if not want:
        return False
    try:
        import time as _time

        from tpu_pbrt.obs.metrics import METRICS

        t0 = _time.perf_counter()
        jax.distributed.initialize()
        # DCN coordination cost is a render-startup phase a fleet
        # monitor wants attributed like any other (host-side registry;
        # no-op under TPU_PBRT_METRICS=0)
        METRICS.gauge(
            "distributed_init_seconds",
            "wall seconds jax.distributed.initialize took",
        ).set(_time.perf_counter() - t0)
        return True
    except (RuntimeError, ValueError) as e:
        # already initialized counts as success
        if "already" in str(e).lower():
            return True
        from tpu_pbrt.utils.error import Warning as _W

        _W(f"jax.distributed.initialize failed: {e}; running single-host")
        return False


def make_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """1-D device mesh over the tile axis (a renderer's parallel axis is
    image/sample space — SURVEY.md §2f maps it to data-parallel)."""
    devs = devices if devices is not None else jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (TILE_AXIS,))


def resolve_mesh(mesh_shape) -> Optional[Mesh]:
    """Options.mesh_shape -> Mesh (or None for single-device): the CLI's
    '--mesh 2,4' spelling resolved against the live device set. Shared
    by the run-to-completion render loop and the render service so both
    frontends mean the same thing by the same flag. A request for more
    devices than exist is an error: rendering on one device what was
    asked of four would hide the missing three."""
    from tpu_pbrt.obs.metrics import METRICS
    from tpu_pbrt.utils.error import PbrtError

    mesh = None
    if mesh_shape:
        n_req = int(np.prod(tuple(mesh_shape)))
        n_have = len(jax.devices())
        if n_req > n_have:
            raise PbrtError(
                f"mesh {tuple(mesh_shape)} needs {n_req} devices but jax "
                f"sees {n_have} ({jax.devices()[0].platform})"
            )
        if n_req > 1:
            mesh = make_mesh(n_req)
    # the mesh width every drain in this process fans over — the
    # denominator a monitor needs next to the per-device wave-spread
    # telemetry (1 = single-device)
    METRICS.gauge(
        "mesh_devices", "devices in the resolved render mesh"
    ).set(1 if mesh is None else mesh.devices.size)
    return mesh


def resolve_pipeline_depth(mesh: Optional[Mesh] = None) -> int:
    """Effective in-flight dispatch window for the drain loops (ISSUE
    13): how many chunk-slices stay launched ahead of the host.
    TPU_PBRT_PIPELINE (default 2), clamped to >= 1 — depth 1 is the
    strictly synchronous dispatch/block/host-work loop, the A/B
    baseline the host_overlap_fraction acceptance compares against.

    The strict non-finite firewall modes (TPU_PBRT_NONFINITE=raise|
    retry) force depth 1: they read each chunk's scrub count before the
    NEXT dispatch may trust the accumulator — a per-chunk device sync
    pipelining cannot hide, and eager checking keeps the failure
    attributed to the exact chunk that scrubbed.

    A mesh does not widen the window: every dispatch spans the whole
    mesh (one SPMD program per chunk), so the in-flight slices are in
    program order regardless of device count. `mesh` is accepted for
    call-site symmetry and future per-topology tuning."""
    from tpu_pbrt.config import cfg

    if cfg.nonfinite != "scrub":
        return 1
    return max(1, int(cfg.pipeline))


#: pixels whose samples make a granule, at most (`work_granule`). On four
#: v5e chips 4 pixels and a whole image row read the same frame time and a
#: granule of the pool's size 4 % more (PERF.md, PR 30): time follows the
#: spread of the devices' rays, so the finest granule that is cheap to
#: address stays
GRANULE_PIXELS = 4


def work_granule(per_dev: int, spp: int, n_dev: int) -> int:
    """Consecutive work items a device takes before the next device's
    turn: the largest divisor of `per_dev` not above the samples of
    GRANULE_PIXELS pixels. Counted in pixels because the work index is
    pixel-major: n_dev granules are a period of the assignment, a run of
    n_dev * GRANULE_PIXELS pixels along an image row at any sample
    count, far narrower than anything on screen, so every device meets
    the object and the sky in the same proportion. It has to DIVIDE
    `per_dev`, which is arbitrary on a small film, or the last round of
    granules would leave holes in the dispatch; 1 always does. With one
    device the whole share is one granule."""
    if n_dev == 1:
        return per_dev
    return max(
        g for g in range(1, min(GRANULE_PIXELS * spp, per_dev) + 1)
        if per_dev % g == 0
    )


def work_item(k, i, n_dev: int, g: int):
    """Who gets what: the dispatch-relative work item of device `i`'s
    local item `k` (0 <= k < per_dev), granules of `g` items dealt
    round-robin. `work_item(k, i) == work_item(0, i) + work_item(k, 0)`:
    the host puts `work_item(0, i)` into a device's start pair and the
    device's body adds `work_item(k, 0)` to it. Plain arithmetic, on
    python ints, numpy and traced values alike; with one device it is
    the identity and traces nothing."""
    if n_dev == 1:
        return k
    return (k // g) * (g * n_dev) + i * g + k % g


def device_spread(values, n_dev: int, axis: str = TILE_AXIS):
    """One-hot scatter of per-device scalars into an (len(values),
    n_dev) block: device i contributes `values` in column i, zeros
    elsewhere, so the drain's EXISTING aux psum reconstructs every
    device's values on every device — an all_gather's result without
    adding a collective (sharded_pool_renderer's no-new-collectives
    contract and the shardcheck SC-LOOP-COLLECTIVE analysis both stay
    untouched).

    This is how the per-device wave count and ray count of the
    independent pool drains leave the mesh step (obs/counters.
    spread_stats turns a row into min/max/rel_spread on the host: waves
    say how long each drain's loop ran, rays how much it traced — a wave
    of rays that miss everything counts as one wave and costs next to
    nothing). Call only inside a shard_map body."""
    i = jax.lax.axis_index(axis)
    col = jnp.stack([jnp.asarray(v, jnp.int32) for v in values])
    return jnp.zeros((len(values), n_dev), jnp.int32).at[:, i].set(col)


def _psum_apart(contrib, aux):
    """The step's two all-reduces, each under its own phase scope: a
    device that has drained its share waits at the FIRST collective it
    reaches for the slowest device, and a trace can only say which one
    that is if the two carry different names."""
    with jax.named_scope(ph.MESH_PSUM_FILM):
        contrib = jax.tree.map(lambda x: jax.lax.psum(x, TILE_AXIS), contrib)
    with jax.named_scope(ph.MESH_PSUM_AUX):
        aux = jax.tree.map(lambda x: jax.lax.psum(x, TILE_AXIS), aux)
    return contrib, aux


def sharded_chunk_renderer(mesh: Mesh, per_device_fn):
    """Wrap a per-device chunk body into an SPMD step with film all-reduce.

    per_device_fn(dev, start_scalar) -> (film_contrib pytree, aux pytree):
    the film contribution of that device's work-items plus scalar
    accounting (nrays, and the firewall's non-finite scrub count when
    telemetry is on). The wrapped function takes (dev, starts (n_dev,))
    with starts sharded over the mesh and returns the psum-merged
    (film_contrib, aux), replicated — ready to add into the accumulated
    film state.

    Failure model (ISSUE 5): there is no per-device recovery INSIDE the
    SPMD step — a lost device fails the whole dispatch (the host sees a
    JaxRuntimeError), and the render loop's recovery ladder handles it
    as a state-poisoning chunk failure: rollback to the last durable
    checkpoint (or restart) + capped-backoff re-dispatch. Chunks are
    idempotent, so the re-run on the surviving mesh is exact. The chaos
    plan's `mesh:lost@chunk=N` injects exactly this shape on the CPU
    mesh; true degraded-mesh continuation (re-forming a smaller mesh
    without a restart) is a ROADMAP open item pending live hardware."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(TILE_AXIS)),
        out_specs=(P(), P()),
    )
    def step(dev, starts):
        contrib, aux = per_device_fn(dev, starts)
        return _psum_apart(contrib, aux)

    return step


def sharded_pool_renderer(mesh: Mesh, per_device_drain):
    """Persistent-wavefront (in-place regeneration) analog of
    sharded_chunk_renderer: each device DRAINS its own share of the
    dispatch through a resident path pool driven by a per-device work
    counter, instead of advancing one static batch in lockstep.

    per_device_drain(dev, start_pair) -> (film_contrib pytree, aux pytree)
    runs the whole drain loop for that device's share: the granules
    `work_item` deals it, every n_dev-th granule of the dispatch from
    `start_pair` on, so the shares cost the same to within a granule's
    variation and no device has to ask another for work. There are NO
    collectives inside the drain, so the SPMD while_loops are free to run
    different iteration counts per device — a device whose paths die
    early regenerates new pixels from its counter and finishes its share
    in fewer waves rather than idling on the longest path; the film psum
    after the drain is the only sync point, and what a device waits
    there is what the shares still differ by. aux (ray/occupancy
    counters, the per-device spread block) is psum-reduced alongside the
    film."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(TILE_AXIS)),
        out_specs=(P(), P()),
    )
    def step(dev, starts):
        contrib, aux = per_device_drain(dev, starts)
        return _psum_apart(contrib, aux)

    return step
