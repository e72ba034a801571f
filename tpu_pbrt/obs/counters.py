"""Device-side per-wave counter block for the persistent-wavefront drain.

The counters are pure `jnp` state carried through the `pool_chunk`
while_loop (and updated per wave inside `_bounce_wave`), psum-merged
across devices by the mesh drain, and fetched ONCE at the drain boundary
together with the ray/occupancy aux — never mid-loop, so the bounce loop
stays clean under `jax.transfer_guard("disallow")` and adds zero
retraces (the jaxpr-audit gates keep watching both).

Kill switch: `TPU_PBRT_TELEMETRY=0`. A disabled counter block is carried
as `None`, which is an EMPTY jax pytree — the loop carry contributes no
avals and the compiled program is the exact pre-telemetry one, not a
masked variant of it.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: occupancy histogram resolution: bin k counts waves whose live-lane
#: fraction fell in [k/N, (k+1)/N) (a full wave lands in the last bin)
N_OCC_BINS = 8

#: host-dict field names, in WaveCounters field order
HOST_FIELDS = (
    "rays_traced",
    "lanes_regenerated",
    "lanes_terminated",
    "film_deposits",
    "nonfinite_deposits",
    "occupancy_histogram",
    "stream_traversals",
    "stream_rounds",
    "stream_pairs_expanded",
    "stream_leaf_tests",
    "stream_pairs_dropped",
    "brute_rays",
    "stream_block_slots",
    "halton_pairs",
    "stream_pairs_deferred",
    "light_picks",
    "light_table_reads",
)


class WaveCounters(NamedTuple):
    """Per-drain counter block; every field is an int32 device scalar
    except the occupancy histogram (int32 [N_OCC_BINS])."""

    #: rays traced (camera continuations + shadow + BSSRDF probe rays)
    rays: jnp.ndarray
    #: pool lanes refilled with fresh camera rays from the work counter
    regenerated: jnp.ndarray
    #: lanes whose path died this wave (miss / RR kill / maxdepth)
    terminated: jnp.ndarray
    #: film deposits (terminated lanes whose pending NEE also settled)
    deposits: jnp.ndarray
    #: deposits whose radiance carried NaN/Inf and was scrubbed to zero
    #: by the film's non-finite firewall (ISSUE 5: one bad wave must not
    #: silently poison every later checkpoint — > 0 here is the signal)
    nonfinite: jnp.ndarray
    #: per-wave occupancy histogram (live lanes / pool width at trace time)
    occ_hist: jnp.ndarray
    # -- stream-tracer work, counted where it happens (accel/stream.py
    # `_SState`) and summed over the pool's 2R waves; the BSSRDF probe
    # traversals of subsurface scenes are not counted
    #: traversals (one per wave)
    st_trav: jnp.ndarray
    #: loop rounds: EXPANDs + FLUSHes (`_SState.iters`)
    st_rounds: jnp.ndarray
    #: (ray, node) pairs expanded (`n_exp`)
    st_pairs: jnp.ndarray
    #: (ray, treelet) block-slot leaf tests (`n_tl`)
    st_leaf: jnp.ndarray
    #: pairs lost to worklist capacity (`n_drop`): 0, or false misses
    st_drop: jnp.ndarray
    # -- brute tracer (accel/mxu.py `brute_intersect`), summed like the
    # stream counters over the pool's 2R waves
    #: live rays (closest and shadow) that met every triangle of the scene
    br_rays: jnp.ndarray
    #: ray slots of the stream tracer's flush trips, filled or not
    #: (`n_bs`; `st_leaf` over this is the blocks' fill). None, an empty
    #: pytree, where no stream tracer runs: those programs carry nothing
    #: for it
    st_slots: Optional[jnp.ndarray] = None
    #: 2D draws of the halton sampler that live lanes' bounces made (two a
    #: lane a wave: the light's uv and the BSDF's uv, each a pair of
    #: scrambled radical inverses; the camera's lens pair is not counted:
    #: a pinhole never reads it). None, an empty pytree, where the sampler
    #: is another: those programs carry nothing for it
    hl_pairs: Optional[jnp.ndarray] = None
    #: pairs EXPAND put back on the stack because more of their children
    #: were hit than its sort keeps rows (`n_def`; each is counted in
    #: `st_pairs` again when it is popped again). None where no stream
    #: tracer runs, like `st_slots`
    st_def: Optional[jnp.ndarray] = None
    # -- the light layer (core/lights_dev.py), counted in `_bounce_wave`.
    # None, an empty pytree, where the light table is at most
    # `MAX_DENSE_ROWS` rows and the dense select serves it: those programs
    # carry nothing for it (and stay what they were, to the character)
    #: lights picked by live lanes: one a lane at a vertex that may scatter
    lt_picks: Optional[jnp.ndarray] = None
    #: elements those lanes read of the light tables: the distribution's
    #: table for the pick and for the MIS pdf of a hit on an emitter, and
    #: the packed light rows (`lights_dev.pick_reads`, `emit_reads`: static
    #: factors, the search's pivots and steps and one row, times the lanes)
    lt_reads: Optional[jnp.ndarray] = None


def enabled() -> bool:
    """The kill-switch gate — a STATIC Python decision at trace time."""
    from tpu_pbrt.config import cfg

    return bool(cfg.telemetry)


def zeros(stream: bool = True, halton: bool = False, light: bool = False) -> WaveCounters:
    """Fresh counter block (call inside jit: the arrays are staged).
    `stream`: whether the scene is stream-traced (see `st_slots`, `st_def`);
    `halton`: whether its sampler is halton (see `hl_pairs`); `light`:
    whether a light is one packed row of its table (see `lt_picks`)."""
    z = jnp.int32(0)
    return WaveCounters(
        rays=z,
        regenerated=z,
        terminated=z,
        deposits=z,
        nonfinite=z,
        occ_hist=jnp.zeros((N_OCC_BINS,), jnp.int32),
        st_trav=z, st_rounds=z, st_pairs=z, st_leaf=z, st_drop=z,
        br_rays=z,
        st_slots=z if stream else None,
        hl_pairs=z if halton else None,
        st_def=z if stream else None,
        lt_picks=z if light else None,
        lt_reads=z if light else None,
    )


def maybe_zeros(
    stream: bool = True, halton: bool = False, light: bool = False
) -> Optional[WaveCounters]:
    """zeros() when telemetry is on, None (empty pytree) when killed."""
    return zeros(stream, halton, light) if enabled() else None


def light_update(
    ctr: WaveCounters, *, picking, emitting, pick_reads: int, emit_reads: int
) -> WaveCounters:
    """One wave's light picks, from inside `_bounce_wave`: `picking` the
    lanes whose pick may be used (a valid vertex under maxdepth),
    `emitting` those whose hit is weighed against the light's pdf (every
    valid vertex); the two static factors are what ONE such lane reads."""
    picks = jnp.sum(picking, dtype=jnp.int32)
    return ctr._replace(
        lt_picks=ctr.lt_picks + picks,
        lt_reads=ctr.lt_reads + picks * pick_reads + jnp.sum(emitting, dtype=jnp.int32) * emit_reads,
    )


def bounce_update(
    ctr: Optional[WaveCounters], *, alive, rays_before, rays_after,
    pairs_per_lane: int = 0,
) -> Optional[WaveCounters]:
    """One trace wave's worth of counting, from inside `_bounce_wave`:
    rays dispatched this wave and the occupancy-histogram bin of the
    wave's live-lane fraction. `alive` is the pre-trace live mask (the
    lanes that actually cost traversal), rays_before/after the per-lane
    ray accumulators around the wave, `pairs_per_lane` the 2D sampler
    draws a live lane's bounce makes (counted where the block carries
    `hl_pairs`)."""
    if ctr is None:
        return None
    width = alive.shape[0]
    live = jnp.sum(alive, dtype=jnp.int32)
    wave_rays = jnp.sum(rays_after - rays_before, dtype=jnp.int32)
    bin_ix = jnp.clip(live * N_OCC_BINS // width, 0, N_OCC_BINS - 1)
    if ctr.hl_pairs is not None:
        ctr = ctr._replace(hl_pairs=ctr.hl_pairs + pairs_per_lane * live)
    return ctr._replace(
        rays=ctr.rays + wave_rays,
        occ_hist=ctr.occ_hist.at[bin_ix].add(1),
    )


def trace_update(ctr: Optional[WaveCounters], work) -> Optional[WaveCounters]:
    """Fold one 2R wave's work counts into the block, from inside
    `_bounce_wave`: the stream tracer's `StreamWork` (accel/stream.py),
    the brute tracer's `BruteWork` (accel/mxu.py), or None where another
    acceleration structure traced the wave."""
    if ctr is None or work is None:
        return ctr
    if not hasattr(work, "rounds"):
        return ctr._replace(br_rays=ctr.br_rays + work.rays)
    return ctr._replace(
        st_trav=ctr.st_trav + 1,
        st_rounds=ctr.st_rounds + work.rounds,
        st_pairs=ctr.st_pairs + work.pairs_expanded,
        st_leaf=ctr.st_leaf + work.leaf_tests,
        st_drop=ctr.st_drop + work.pairs_dropped,
        st_slots=ctr.st_slots + work.block_slots,
        st_def=ctr.st_def + work.pairs_deferred,
    )


def pool_update(
    ctr: Optional[WaveCounters], *, regenerated, terminated, deposits,
    nonfinite=None,
) -> Optional[WaveCounters]:
    """The drain-loop structural counters, from the `pool_chunk` body:
    each argument is this wave's int32 count. nonfinite is the firewall's
    scrubbed-deposit count (None keeps the field untouched)."""
    if ctr is None:
        return None
    upd = ctr._replace(
        regenerated=ctr.regenerated + regenerated,
        terminated=ctr.terminated + terminated,
        deposits=ctr.deposits + deposits,
    )
    if nonfinite is not None:
        upd = upd._replace(nonfinite=ctr.nonfinite + nonfinite)
    return upd


# -- host side (the one fetch at the drain boundary) -----------------------


def to_host(ctrs: Iterable[WaveCounters]) -> Dict[str, Any]:
    """Fetch a list of per-chunk counter blocks with ONE device_get and
    sum them into the canonical host dict (ints + histogram list)."""
    ctrs = list(ctrs)
    if not ctrs:
        return {}
    host = jax.device_get(ctrs)
    out: Dict[str, Any] = {
        k: 0 for k, v in zip(HOST_FIELDS, host[0]) if v is not None
    }
    out["occupancy_histogram"] = [0] * N_OCC_BINS
    for c in host:
        for name, v in zip(HOST_FIELDS, c):
            if v is None:
                continue
            if name == "occupancy_histogram":
                out[name] = [a + int(b) for a, b in zip(out[name], v)]
            else:
                out[name] += int(v)
    return out


def with_brute_pairs(host: Dict[str, Any], n_tris: int) -> Dict[str, Any]:
    """The host dict plus `brute_pairs_tested`. The brute tracer tests
    every ray against every triangle, so the pairs are `brute_rays` times
    the scene's static triangle count (0 where another tracer did the
    work). The product is taken here, in a Python int: on the device a
    2^20-path dispatch of a 256-triangle scene at a deep maxdepth would
    pass an int32 (cornell's 36 x 12.5 M rays a frame is 4.5e8)."""
    if "brute_rays" not in host:
        return host
    return {**host, "brute_pairs_tested": int(host["brute_rays"]) * int(n_tris)}


def merge_host(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    """Sum two host counter dicts (checkpoint-resume seeding: the saved
    cumulative snapshot + this process's drain)."""
    if not a:
        return dict(b)
    if not b:
        return dict(a)
    out: Dict[str, Any] = {}
    for k in set(a) | set(b):
        va, vb = a.get(k), b.get(k)
        if isinstance(va, list) or isinstance(vb, list):
            va = va or []
            vb = vb or []
            n = max(len(va), len(vb))
            va = va + [0] * (n - len(va))
            vb = vb + [0] * (n - len(vb))
            out[k] = [int(x) + int(y) for x, y in zip(va, vb)]
        else:
            out[k] = int(va or 0) + int(vb or 0)
    return out


def spread_stats(per_device, what: str = "waves") -> Dict[str, Any]:
    """Spread of a per-device count of the independent per-device drains
    (the ROADMAP multi-chip metric): how unevenly they ran. `what` names
    the count: "waves" (trips of each drain's loop) or "rays" (what each
    traced: a wave that misses everything is one wave and next to no
    work, so the rays say more about time). rel_spread = (max - min) /
    mean; 0 on a single device or a perfectly even mesh."""
    counts = [int(w) for w in per_device]
    if not counts:
        return {}
    mean = sum(counts) / len(counts)
    return {
        f"per_device_{what}": counts,
        "min": min(counts),
        "max": max(counts),
        "mean": mean,
        "rel_spread": (max(counts) - min(counts)) / max(mean, 1e-9),
    }


def spread_telemetry(blocks, waves: Optional[int], rays: int) -> Dict[str, Any]:
    """The two spread entries of `stats["telemetry"]`, for the render
    loop and the render service alike: from the mesh's per-dispatch
    `parallel/mesh.device_spread` blocks, fetched to the host, or (no
    mesh: `blocks` empty) in their degenerate one-device form from the
    totals the host holds anyway, so the one-device program carries
    nothing for them. `waves` None: no pool drained
    (the fixed-batch loop), and there is nothing to spread."""
    if blocks:
        # one row per count, one column per device, summed over dispatches
        per_waves, per_rays = np.sum(np.stack(blocks), axis=0).tolist()
    elif waves is not None:
        per_waves, per_rays = [waves], [rays]
    else:
        per_waves = per_rays = []
    return {
        "wave_spread": spread_stats(per_waves),
        "ray_spread": spread_stats(per_rays, "rays"),
    }
