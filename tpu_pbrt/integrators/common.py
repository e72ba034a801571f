"""Shared wavefront-integrator machinery.

Capability match for pbrt-v3 src/core/integrator.{h,cpp}:
- Integrator/SamplerIntegrator::Render — the tile loop. TPU-first redesign:
  instead of ParallelFor2D over 16x16 tiles with per-thread FilmTiles, the
  image x spp domain is a flat work index space, cut into fixed-size ray
  batches (<= MAX_RAYS_PER_DISPATCH). Each batch runs one jitted
  ray-gen -> Li -> film-scatter dispatch; film accumulation is associative
  so "tiles" merge by addition. Tiling across devices (shard_map over the
  work axis) is layered on in parallel/ (SURVEY.md §2f).
- UniformSampleOneLight / EstimateDirect (MIS NEE) — estimate_direct here.
- SurfaceInteraction construction (core/interaction.cpp): hit -> position,
  geometric/shading normals, uv, material/light ids.

Sampling convention: every random dimension is a pure function of
(pixel_x, pixel_y, sample_index, dimension_salt) via the counter-based RNG,
with the film dimension using a per-pixel-scrambled (0,2)-sequence — the
wavefront equivalent of pbrt's per-pixel sampler streams.
"""

from __future__ import annotations

import time
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_pbrt.accel.traverse import (
    MAX_RAYS_PER_DISPATCH,
    Hit,
    bvh_intersect,
    bvh_intersect_p,
)
from tpu_pbrt.accel.wide import wide_intersect, wide_intersect_p
from tpu_pbrt.utils.clock import WALL


from tpu_pbrt.cameras import generate_rays
from tpu_pbrt.config import cfg
from tpu_pbrt.core import bxdf
from tpu_pbrt.core import lights_dev as ld
from tpu_pbrt.core.film import FilmState
from tpu_pbrt.obs import phases as ph
from tpu_pbrt.obs.compiles import COMPILES
from tpu_pbrt.parallel.checkpoint import (
    checkpoint_exists,
    load_checkpoint,
    render_fingerprint,
    save_checkpoint,
)
from tpu_pbrt.core.sampling import (
    hash_u32,
    normalize_sampler_name,
    power_heuristic,
    sample_1d,
    sample_2d,
    sobol_2d,
    uniform_float,
)
from tpu_pbrt.core.vecmath import (
    coordinate_system,
    cross,
    dot,
    face_forward,
    normalize,
    offset_ray_origin,
    to_local,
    to_world,
)

def scene_intersect(dev, o, d, t_max, time=None) -> Hit:
    """Scene::Intersect — dispatches to the acceleration structure the
    scene compiler chose: the stream (sort/compaction wavefront) tracer
    (TPU-shaped default, coherence-independent), the every-ray-against-
    every-triangle test for tiny scenes, or the packet/wide/binary walkers
    (TPU_PBRT_BVH=packet|wide|binary). time: per-ray shutter time in
    [0,1] for motion-blur scenes (dev carries tri_verts1)."""
    with jax.named_scope(ph.TRACE_CLOSEST):
        return _closest_hit(dev, o, d, t_max, time)


def _closest_hit(dev, o, d, t_max, time) -> Hit:
    if "tstream" in dev:
        from tpu_pbrt.accel.stream import stream_intersect

        return stream_intersect(
            dev["tstream"], dev["tri_verts"], o, d, t_max,
            time=time, tri_verts1=dev.get("tri_verts1"),
            tv9T=dev.get("tri_verts9T"), tv9T1=dev.get("tri_verts1_9T"),
        )
    if "tpack" in dev:
        from tpu_pbrt.accel.packet import packet_intersect

        return packet_intersect(dev["tpack"], o, d, t_max)
    if "brute" in dev:
        from tpu_pbrt.accel.mxu import brute_intersect

        bt = dev["brute"]
        with jax.named_scope(ph.BRUTE_INTERSECT):
            hit = brute_intersect(
                bt["tab"], o, d, t_max, time=time, tab1=bt.get("tab1")
            )
        if "tri_verts1" in dev and time is not None:
            # shading must see the TIME-EVALUATED triangle, not the
            # shutter-start keyframe make_interaction would refetch
            prim = jnp.maximum(hit.prim, 0)
            tm = jnp.asarray(time, jnp.float32).reshape(-1, 1, 1)
            tv = (1.0 - tm) * dev["tri_verts"][prim] + tm * dev["tri_verts1"][prim]
            hit = hit._replace(tv=tv)
        return hit
    if "wbvh" in dev:
        return wide_intersect(dev["wbvh"], dev["tri_verts"], o, d, t_max)
    return bvh_intersect(dev["bvh"], dev["tri_verts"], o, d, t_max)


def scene_intersect_fused(dev, o, d, t_max, n_cam: int, time=None):
    """Fused camera+shadow closest-hit: full Hit for the first n_cam
    rays, bare prim ids for the tail (queued shadow rays only need
    prim >= 0; skipping their barycentric tri_verts refetch saves ~9
    gathered elements per shadow ray on the stream path). Third: the
    wave's work counts (accel/stream.py StreamWork, accel/mxu.py
    BruteWork), None where another acceleration structure traced it."""
    with jax.named_scope(ph.TRACE_FUSED):
        if "tstream" in dev:
            from tpu_pbrt.accel.stream import stream_intersect_split

            return stream_intersect_split(
                dev["tstream"], dev["tri_verts"], o, d, t_max, n_cam,
                time=time, tri_verts1=dev.get("tri_verts1"),
                tv9T=dev.get("tri_verts9T"), tv9T1=dev.get("tri_verts1_9T"),
            )
        hit = _closest_hit(dev, o, d, t_max, time)
        work = None
        if "brute" in dev:
            from tpu_pbrt.accel.mxu import BruteWork

            work = BruteWork(jnp.sum(t_max > 0.0, dtype=jnp.int32))
        return jax.tree.map(lambda a: a[:n_cam], hit), hit.prim[n_cam:], work


def scene_intersect_p(dev, o, d, t_max, time=None):
    """Scene::IntersectP — shadow-ray predicate."""
    with jax.named_scope(ph.TRACE_SHADOW):
        return _any_hit(dev, o, d, t_max, time)


def _any_hit(dev, o, d, t_max, time):
    if "tstream" in dev:
        from tpu_pbrt.accel.stream import stream_intersect_p

        return stream_intersect_p(dev["tstream"], o, d, t_max, time=time)
    if "tpack" in dev:
        from tpu_pbrt.accel.packet import packet_intersect_p

        return packet_intersect_p(dev["tpack"], o, d, t_max)
    if "brute" in dev:
        return _closest_hit(dev, o, d, t_max, None).prim >= 0
    if "wbvh" in dev:
        return wide_intersect_p(dev["wbvh"], dev["tri_verts"], o, d, t_max)
    return bvh_intersect_p(dev["bvh"], dev["tri_verts"], o, d, t_max)


def unoccluded_tr(dev, o, d, dist, cur_med, px, py, s, salt, segments=1):
    """VisibilityTester::Unoccluded/Tr (light.cpp): is the light sample
    visible, and with what transmittance?

    pbrt's Tr walk passes THROUGH null-BSDF surfaces (medium-interface
    container geometry), accumulating each sub-segment's medium
    transmittance and switching media at the crossing; only real-material
    hits occlude (ADVICE r1: MAT_NONE shapes must not block in-medium NEE).

    segments=1 is the cheap any-hit path for scenes with no null materials.
    cur_med None skips transmittance entirely (no media in flight).
    Returns (visible (R,), tr (R,3))."""
    from tpu_pbrt.core import media as md
    from tpu_pbrt.scene.compiler import MAT_NONE

    shape = o.shape[:-1]
    tr = jnp.ones(shape + (3,), jnp.float32)
    remaining = jnp.broadcast_to(jnp.asarray(dist, jnp.float32), shape) * 0.999
    mt = dev.get("media") if cur_med is not None else None

    if segments == 1:
        occluded = scene_intersect_p(dev, o, d, remaining)
        if mt is not None:
            med = jnp.where(~occluded, jnp.broadcast_to(cur_med, shape), -1)
            tr = md.medium_tr(mt, med, o, d, remaining, px, py, s, salt)
        return ~occluded, tr

    med = (
        jnp.broadcast_to(jnp.asarray(cur_med, jnp.int32), shape)
        if cur_med is not None
        else jnp.full(shape, -1, jnp.int32)
    )
    oo = o
    visible = jnp.zeros(shape, bool)
    active = jnp.ones(shape, bool)
    for k in range(segments):
        with jax.named_scope(ph.TRACE_SHADOW):
            hit = _closest_hit(dev, oo, d, remaining, None)
        hit_any = active & (hit.prim >= 0)
        prim = jnp.maximum(hit.prim, 0)
        # tri_mat holds material-table indices; the null test is on the type
        is_null = hit_any & (dev["mat"]["type"][dev["tri_mat"][prim]] == MAT_NONE)
        seg_len = jnp.where(hit_any, hit.t, remaining)
        if mt is not None:
            tr_seg = md.medium_tr(
                mt, jnp.where(active, med, -1), oo, d, seg_len, px, py, s, salt + 7 * k
            )
            tr = jnp.where(active[..., None], tr * tr_seg, tr)
        visible = visible | (active & ~hit_any)
        # step past null interfaces, flipping the medium at the crossing
        step = is_null
        tv = dev["tri_verts"][prim]
        ng = normalize(cross(tv[..., 1, :] - tv[..., 0, :], tv[..., 2, :] - tv[..., 0, :]))
        going_in = dot(d, ng) < 0.0
        new_med = jnp.where(going_in, dev["tri_med_in"][prim], dev["tri_med_out"][prim])
        med = jnp.where(step, new_med, med)
        p_hit = oo + hit.t[..., None] * d
        oo = jnp.where(step[..., None], offset_ray_origin(p_hit, ng, d), oo)
        remaining = jnp.where(step, remaining - hit.t, remaining)
        active = step
    # lanes that ran out of segments while still inside null nesting count
    # as occluded (conservative; PASSTHROUGH_MARGIN bounds real scenes)
    return visible, tr


# dimension salts (one stream per logical sampler dimension; bounce-shifted)
DIM_FILM_X = 0
DIM_LENS = 2
DIM_TIME = 3
DIM_LIGHT_PICK = 4
DIM_LIGHT_UV = 5
DIM_BSDF_LOBE = 7
DIM_BSDF_UV = 8
DIM_RR = 10
DIM_MIX = 11
DIMS_PER_BOUNCE = 16


class ChunkDispatchError(RuntimeError):
    """A chunk dispatch failed (worker/device loss). poisons_state=True
    means the in-flight film accumulator cannot be trusted (mid-dispatch
    loss) and recovery must roll back to the last checkpoint; False means
    the dispatch never ran and a plain re-dispatch is exact."""

    def __init__(self, msg="chunk dispatch failed", poisons_state=False):
        super().__init__(msg)
        self.poisons_state = poisons_state


class ChunkCompileError(RuntimeError):
    """The chunk program could not be BUILT: the trace or XLA refused
    it. Deterministic — a re-dispatch would build the same program and
    fail the same way — so it is deliberately NOT a ChunkDispatchError:
    it passes through the recovery ladders and fails the render (or the
    serve job) on the first attempt, carrying the compiler's own
    message."""


class NonFiniteWaveError(ChunkDispatchError):
    """The non-finite firewall found scrubbed deposits in a chunk under
    TPU_PBRT_NONFINITE=retry: the accumulated film holds ZEROED
    contributions where real radiance belonged, so the chunk counts as
    state-poisoning and recovery re-renders it exactly (rollback or
    restart + re-dispatch; the chaos nan injection fires once, so the
    re-run is clean and the final film bit-identical)."""

    def __init__(self, msg):
        super().__init__(msg, poisons_state=True)


class NonFiniteRadianceError(RuntimeError):
    """TPU_PBRT_NONFINITE=raise: a chunk deposited NaN/Inf radiance (the
    firewall scrubbed it, but strict mode treats any contamination as a
    hard error — debugging shaders/scenes where a silent zero would hide
    the bug)."""


def redispatch_backoff(chunk: int, attempt: int) -> float:
    """Seconds to sleep before re-dispatch `attempt` (1-based) of
    `chunk`: capped exponential backoff with DETERMINISTIC jitter —
    min(base * 2^(attempt-1), cap) scaled into [0.5, 1.0] by a hash of
    (chunk, attempt), so chaos-matrix recoveries are reproducible while
    real fleet retries still decorrelate across chunks. The tight
    no-backoff loop this replaces once let a hung backend eat a whole
    capture's budget in retries."""
    base = float(cfg.retry_backoff)
    cap = float(cfg.retry_backoff_cap)
    if base <= 0.0:
        return 0.0
    b = min(base * (2.0 ** max(attempt - 1, 0)), cap)
    frac = (zlib.crc32(f"{chunk}:{attempt}".encode()) & 0xFFFF) / 65535.0
    return b * (0.5 + 0.5 * frac)


def live_film_carries(depth: int) -> int:
    """Worst-case simultaneously-LIVE film accumulator buffers for one
    job dispatching through a depth-N window — the shared term of
    hbmcheck's static HBM model (HC-CAP/HC-ALIAS) and protocheck's
    PROTO-HBM dynamic watermark. Depth 1 compiles donation into the
    chunk closure: input and output alias, ONE buffer. Depth > 1
    compiles donation OUT (a deferred checkpoint snapshot may still
    read a superseded carry), so each of the ``depth`` in-flight slices
    pins its un-donated input carry, plus the newest output: depth + 1."""
    d = max(1, int(depth))
    return 1 if d == 1 else d + 1


class DispatchWindow:
    """Bounded in-flight window of dispatched chunk-slices (ISSUE 13 /
    ROADMAP #2 — the refactor every other speed item inherits).

    JAX dispatch is async: ``plan.dispatch`` returns device futures
    immediately. This class gives that asynchrony structure: keep up to
    ``depth`` slices launched ahead, and RETIRE the oldest (block on
    its per-chunk sync handle) only when the window is full — so all
    host-side work between dispatches (deposit bookkeeping, preview
    develop, checkpoint serialization, WFQ scheduling, metrics/flight/
    trace recording) runs UNDER the device compute of the slices still
    in flight. ``depth`` 1 reproduces the strictly synchronous
    dispatch/block/host-work loop — the A/B baseline the
    ``host_overlap_fraction`` acceptance compares against. Bit-identity
    across depths holds by construction: the window moves SYNC POINTS,
    never the dispatched programs or their order.

    Deferred actions (``defer``) run once their cursor's slice has
    retired — the checkpoint path snapshots the film accumulator
    device-side at enqueue time (``parallel/checkpoint.film_snapshot``;
    the live accumulator is donated into the next dispatch) and
    serializes the snapshot to disk under in-flight compute.

    Error contract: a device failure surfacing at a retire sync is
    re-raised as ``ChunkDispatchError(poisons_state=True)`` so the
    caller's recovery ladder handles it like a mid-dispatch loss; on
    ANY ChunkDispatchError the caller calls ``flush`` before the ladder
    — poisoning failures discard the window outright (the rollback/
    restart re-renders everything it covered), clean failures quiesce
    it (block on the survivors, run the deferred durable writes) so
    completed work is never lost to an unrelated chunk's retry streak.
    """

    __slots__ = (
        "depth", "slices", "deferred", "on_wait", "span_name", "clock",
    )

    def __init__(
        self, depth: int, on_wait=None, span_name: str = "dispatch/retire",
        clock=None,
    ):
        self.depth = max(1, int(depth))
        #: [(chunk index, per-chunk device sync handle, trace span|None)]
        self.slices: list = []
        #: [(cursor, fn)] — fn() runs once chunk cursor-1 has retired
        self.deferred: list = []
        self.on_wait = on_wait  # dt -> None (device_wait attribution)
        self.span_name = span_name
        # injected time source (utils/clock.py) — only for device-wait
        # attribution, but under a VirtualClock even measurement must
        # not touch the wall (protocheck's determinism contract). None:
        # the wait is the retire span's own duration (one clock read)
        self.clock = clock

    def __len__(self) -> int:
        return len(self.slices)

    def push(self, chunk: int, handle, span=None) -> None:
        """`span` (tpu-scope): the async-span descriptor the caller
        opened at dispatch enqueue — {"name", "id", "cat", optional
        "flow"/"flow_name", "trace_id", "span_id"} — which the window
        closes at the slice's retire sync (or its discard), so the
        in-flight lifetime renders as one causally-bound track however
        deep the pipeline runs."""
        self.slices.append((chunk, handle, span))

    @staticmethod
    def _close_span(span, ok: bool) -> None:
        if not span:
            return
        from tpu_pbrt.obs.trace import TRACE

        fid = span.get("flow")
        if fid:
            TRACE.flow_finish(
                span.get("flow_name", "slice_flow"), id=fid, ok=ok
            )
        TRACE.async_end(
            span["name"], id=span["id"], cat=span.get("cat", "slice"), ok=ok
        )

    def close_spans(self, ok: bool) -> None:
        """Close every in-flight slice's span WITHOUT retiring — for
        callers that sync the whole job another way (the serve park/
        finalize paths block on the film state, which transitively
        blocks on every in-flight slice) and then drop the window. The
        handles stay; later flush/drain sees the spans already closed."""
        for i, (chunk, handle, span) in enumerate(self.slices):
            self._close_span(span, ok)
            self.slices[i] = (chunk, handle, None)

    def defer(self, cursor: int, fn) -> None:
        self.deferred.append((cursor, fn))

    def full(self) -> bool:
        return len(self.slices) >= self.depth

    def retire_one(self) -> int:
        """Block on the OLDEST in-flight slice (the device_wait phase),
        then run every deferred action whose cursor has retired.
        Returns the retired chunk index."""
        chunk, handle, span = self.slices.pop(0)
        from tpu_pbrt.obs.trace import TRACE

        targs = {
            k: span[k]
            for k in ("trace_id", "span_id")
            if span and k in span
        }
        t0 = None if self.clock is None else self.clock.monotonic()
        ok = False
        try:
            # the span is opened whether or not a trace file is asked
            # for: inside any jax.profiler trace it names what the host
            # was doing while the device had a gap
            with TRACE.span(self.span_name, chunk=chunk, **targs) as wait:
                jax.block_until_ready(handle)
            ok = True
        except jax.errors.JaxRuntimeError as e:
            raise ChunkDispatchError(
                f"in-flight slice {chunk} failed: {e}", poisons_state=True
            ) from e
        finally:
            if self.on_wait is not None:
                self.on_wait(
                    wait.seconds if t0 is None
                    else self.clock.monotonic() - t0
                )
            self._close_span(span, ok)
        while self.deferred and self.deferred[0][0] <= chunk + 1:
            self.deferred.pop(0)[1]()
        return chunk

    def drain(self) -> None:
        """Retire everything in flight and run every deferred action."""
        while self.slices:
            self.retire_one()
        while self.deferred:
            self.deferred.pop(0)[1]()

    def flush(self, discard: bool = False) -> None:
        """Error-path teardown (see the class docstring). discard=True
        drops handles and deferred actions without touching the device;
        discard=False drains — and any latent async failure surfaces
        HERE, inside the caller's ladder, as a poisoning
        ChunkDispatchError with the window already cleared."""
        if discard:
            # close (not leak) the in-flight spans: the validator's
            # pairing invariant holds on error paths too, and the
            # timeline records WHICH slices the rollback threw away
            for _, _, span in self.slices:
                self._close_span(span, ok=False)
            self.slices.clear()
            self.deferred.clear()
            return
        try:
            self.drain()
        finally:
            for _, _, span in self.slices:
                self._close_span(span, ok=False)
            self.slices.clear()
            self.deferred.clear()


def _fixed_batch_nonfinite(p_film, L):
    """Non-finite-firewall count for the fixed-batch deposit paths: rows
    the film is about to scrub, restricted to valid work items (body()
    parks the final chunk's invalid tail at p_film = -1e6). Returns None
    when telemetry is killed so the compiled program stays the exact
    pre-telemetry one."""
    # direct import (not the module-attr spelling): keeps jaxlint's
    # by-name call graph from conflating this kill-switch gate with the
    # unrelated `.enabled` recorder properties
    from tpu_pbrt.obs.counters import enabled

    if not enabled():
        return None
    from tpu_pbrt.core.film import nonfinite_mask

    valid = p_film[..., 0] > -1e5
    return jnp.sum(nonfinite_mask(L) & valid, dtype=jnp.int32)


@dataclass
class ChunkPlan:
    """The chunked decomposition of one render's work domain plus the
    (cached) jitted dispatch closure — everything needed to advance a
    render one idempotent chunk at a time.

    This is the submit/step seam the render service (tpu_pbrt/serve)
    schedules on: ``dispatch(state, c)`` runs chunk ``c`` against a film
    accumulator and returns the new state + accounting aux, and the
    (film state, chunk cursor, rays, counters) tuple a caller carries
    between dispatches is exactly the checkpoint-v4 payload — so any
    job can be parked mid-render (emergency checkpoint, PR 5's path)
    and resumed with no lost work. ``WavefrontIntegrator.render`` below
    is one scheduling policy over this plan (run to completion with the
    recovery ladder); the multi-tenant service loop is another."""

    integrator: Any
    scene: Any
    mesh: Any
    film: Any
    cam: Any
    chunk: int
    per_dev: int
    n_dev: int
    n_chunks: int
    spp: int
    total: int
    npix: int
    bounds: tuple  # film sample bounds (x0, x1, y0, y1)
    pool: int
    use_regen: bool
    chaos_nan: bool
    starts: list
    jfn: Any
    fingerprint: str
    #: in-flight window depth the closure compiled for (ISSUE 13):
    #: depth 1 donates the film carry (the zero-copy in-place chain,
    #: byte-for-byte the pre-pipeline program); depth > 1 compiles
    #: WITHOUT donation so the carry pipelines as a true async enqueue
    #: and the previous accumulator stays readable for deferred
    #: checkpoint writes — see prepare_chunks for the full rationale
    pipeline_depth: int = 1

    def dispatch(self, state, c: int):
        """Dispatch chunk ``c`` against ``state``. At pipeline_depth 1
        the film accumulator is DONATED — callers must use the returned
        state and never touch the argument again; at depth > 1 the
        closure compiled without donation and ``state`` stays readable
        (the deferred-checkpoint contract). Returns (state, aux)."""
        st = self.starts[c]
        if self.mesh is not None:
            # the merged film comes back replicated over the mesh; a
            # fresh (or checkpoint-loaded) accumulator sits on one
            # device, and fed as it is the SECOND dispatch would see
            # another input sharding and build the whole program again
            # (seen on four v5e chips, PR 21). Already-replicated state
            # passes through untouched.
            state = jax.device_put(state, NamedSharding(self.mesh, P()))
            args = (st,)
        elif self.chaos_nan:
            from tpu_pbrt.chaos import CHAOS

            nanw = jax.device_put(np.int32(CHAOS.nan_wave_for(c)))
            args = (st[0], st[1], nanw)
        else:
            args = (st[0], st[1])
        traces = COMPILES.traces
        try:
            return self.jfn(state, self.scene.dev, *args)
        except Exception as e:
            # a call that traced anything was BUILDING its program, and
            # what it raised — from the trace (Python exceptions) or
            # from XLA (a JaxRuntimeError) — it will raise again on
            # every attempt.
            # A call that traced nothing only executed: its errors are
            # the device's and go to the caller's recovery ladder.
            if COMPILES.traces == traces:
                raise
            raise ChunkCompileError(
                f"chunk program failed to build "
                f"(chunk={self.chunk}, pool={self.pool}): "
                f"{type(e).__name__}: {e}"
            ) from e

    def aux_parts(self, aux):
        """Split a dispatch's aux into (nrays, occ, ctr, spread, nf):
        occ = (live, waves, truncated) on the regen path, ctr/spread
        the telemetry blocks (None when killed), nf the fixed-batch
        firewall scrub count. Mirrors render()'s inline unpacking for
        other schedulers (the render service)."""
        if self.use_regen:
            nrays = aux[0]
            occ = tuple(aux[1:4])
            ctr = aux[4] if len(aux) > 4 else None
            spread = aux[5] if len(aux) > 5 else None
            return nrays, occ, ctr, spread, None
        if isinstance(aux, tuple):
            return aux[0], None, None, None, aux[1]
        return aux, None, None, None, None

    def capacity_audit(self):
        """Pre-render stream-capacity audit (DEFAULT ON — an overflow
        must fail in seconds, not after the full render has been paid
        for): re-trace one camera-ray chunk through the stats variant of
        the stream tracer and FAIL loudly if any traversal pair was
        dropped to capacity (silent false misses otherwise). Audits the
        primary wave only — bounce waves produce FEWER simultaneous
        pairs (dead lanes cull at init), so the camera wave bounds the
        live worklist for a given chunk size. TPU_PBRT_AUDIT_DROPS=0
        opts out; the drop count is memoized per (scene, chunk) so
        repeat preparations (warm service resubmits) pay nothing."""
        dev = self.scene.dev
        if not cfg.audit_drops or "tstream" not in dev:
            return
        integ = self.integrator
        memo = getattr(integ, "_audit_memo", None)
        if memo is None:
            memo = integ._audit_memo = {}
        # CompiledScene is not hashable: key by identity, keep the strong
        # ref in the value so the id can never be recycled under the memo
        audit_key = (self.scene, self.chunk)
        memo_key = (id(self.scene), self.chunk)
        if memo_key in memo:
            drops = memo[memo_key][1]
        else:
            from tpu_pbrt.accel.stream import stream_traverse_stats
            from tpu_pbrt.obs.trace import TRACE

            x0, _, y0, _ = self.bounds
            w = self.bounds[1] - self.bounds[0]
            chunk, total, spp, cam = self.chunk, self.total, self.spp, self.cam
            cached_audit = getattr(integ, "_audit_jit", None)
            if (
                cached_audit is not None
                and cached_audit[0][0] is self.scene
                and cached_audit[0][1] == chunk
            ):
                audit_rays = cached_audit[1]
            else:

                @jax.jit
                def audit_rays():
                    # staged under jit: eager array creation would be an
                    # implicit transfer under the audit's transfer guard.
                    # Cached across render() calls (like the chunk
                    # closure) so repeat renders stay at 0 recompiles.
                    k = jnp.arange(min(chunk, total), dtype=jnp.int32)
                    pix = k // spp
                    p_film0 = jnp.stack(
                        [(x0 + pix % w).astype(jnp.float32) + 0.5,
                         (y0 + pix // w).astype(jnp.float32) + 0.5], axis=-1)
                    o0, d0, _ = generate_rays(
                        cam, p_film0, jnp.zeros_like(p_film0)
                    )
                    return o0, d0

                integ._audit_jit = (audit_key, audit_rays)

            with TRACE.span("render/capacity_audit") as sp, COMPILES.stages_into(sp):
                o0, d0 = audit_rays()
                work = stream_traverse_stats(
                    dev["tstream"], o0, d0,
                    jax.device_put(np.float32(np.inf)),
                )
                drops = int(jax.device_get(work.pairs_dropped))
            memo[memo_key] = (self.scene, drops)
        if drops > 0:
            msg = (
                f"stream tracer dropped {drops} traversal pairs to "
                "capacity on the camera wave — the render may have false "
                "misses; lower TPU_PBRT_CHUNK or raise TPU_PBRT_HEADROOM"
            )
            if cfg.allow_drops:
                from tpu_pbrt.utils.error import Warning as _W

                _W(msg)
            else:
                raise RuntimeError(msg)


@dataclass
class RenderResult:
    image: np.ndarray
    film_state: Any
    seconds: float
    rays_traced: int
    mray_per_sec: float
    spp: int
    #: fraction of the work domain actually rendered (< 1.0 when a
    #: max_seconds budget stopped the loop early; the image is a partial,
    #: noisier render but Mray/s is still a valid steady-state measurement)
    completed_fraction: float = 1.0
    stats: Dict[str, Any] = field(default_factory=dict)


class Interaction:
    """SoA surface interaction for a ray batch."""

    __slots__ = ("p", "ng", "ns", "ss", "ts", "uv", "mat", "light", "wo", "valid")

    def __init__(self, p, ng, ns, ss, ts, uv, mat, light, wo, valid):
        self.p = p
        self.ng = ng
        self.ns = ns
        self.ss = ss  # shading tangent
        self.ts = ts  # shading bitangent
        self.uv = uv
        self.mat = mat
        self.light = light
        self.wo = wo
        self.valid = valid


def make_interaction(dev, hit: Hit, o, d) -> Interaction:
    """Hit records -> surface interaction (interaction.cpp SurfaceInteraction
    + triangle.cpp's normal/uv interpolation)."""
    with jax.named_scope(ph.SHADE_INTERACTION):
        return _interaction(dev, hit, d)


def _interaction(dev, hit: Hit, d) -> Interaction:
    prim = jnp.maximum(hit.prim, 0)
    # the tracer already fetched the hit vertices (Hit.tv) — re-gathering
    # tri_verts costs ~9 gathered elements/ray on TPU
    tv = hit.tv if hit.tv is not None else dev["tri_verts"][prim]
    if "tri_sh16" in dev:
        # one lane-major (16, T) take: normals, uvs, packed ids
        sh = jnp.take(dev["tri_sh16"], prim, axis=1)  # (16, R)
        shT = jnp.moveaxis(sh, 0, -1)  # (..., 16)
        tn = shT[..., 0:9].reshape(shT.shape[:-1] + (3, 3))
        tuv = shT[..., 9:15].reshape(shT.shape[:-1] + (3, 2))
        from tpu_pbrt.scene.compiler import packed_id_base

        packed = sh[15].astype(jnp.int32)
        id_base = packed_id_base(dev["light"]["type"].shape[0])
        mat_id = packed // id_base
        light_id = packed % id_base - 1
    else:
        tn = dev["tri_normals"][prim]
        tuv = dev["tri_uvs"][prim]
        mat_id = dev["tri_mat"][prim]
        light_id = dev["tri_light"][prim]
    b0 = hit.b0
    b1 = hit.b1
    b2 = 1.0 - b0 - b1
    p = b0[..., None] * tv[..., 0, :] + b1[..., None] * tv[..., 1, :] + b2[..., None] * tv[..., 2, :]
    e1 = tv[..., 1, :] - tv[..., 0, :]
    e2 = tv[..., 2, :] - tv[..., 0, :]
    ng = normalize(cross(e1, e2))
    ns = b0[..., None] * tn[..., 0, :] + b1[..., None] * tn[..., 1, :] + b2[..., None] * tn[..., 2, :]
    ns_len = jnp.linalg.norm(ns, axis=-1, keepdims=True)
    ns = jnp.where(ns_len > 1e-12, ns / jnp.maximum(ns_len, 1e-20), ng)
    # orient geometric normal to the shading normal's hemisphere
    ng = face_forward(ng, ns)
    uv = b0[..., None] * tuv[..., 0, :] + b1[..., None] * tuv[..., 1, :] + b2[..., None] * tuv[..., 2, :]
    if "tri_tanT" in dev:
        # uv-aligned shading tangent (triangle.cpp dpdu) — required by
        # the hair BSDF (x axis along the curve); built only when the
        # scene needs it, else the cheap arbitrary frame below
        tan = jnp.moveaxis(jnp.take(dev["tri_tanT"], prim, axis=1), 0, -1)
        tan = tan - ns * jnp.sum(tan * ns, axis=-1, keepdims=True)
        tl = jnp.linalg.norm(tan, axis=-1, keepdims=True)
        ss0, ts0 = coordinate_system(ns)
        ok = tl[..., 0] > 1e-8
        ss = jnp.where(ok[..., None], tan / jnp.maximum(tl, 1e-20), ss0)
        ts = jnp.where(ok[..., None], cross(ns, ss), ts0)
    else:
        ss, ts = coordinate_system(ns)
    return Interaction(
        p=p,
        ng=ng,
        ns=ns,
        ss=ss,
        ts=ts,
        uv=uv,
        mat=mat_id,
        light=light_id,
        wo=-d,
        valid=hit.prim >= 0,
    )


def texture_footprint(dev, it_prim, p_hit, ng, o, d, dox, ddx, doy, ddy):
    """SurfaceInteraction::ComputeDifferentials (interaction.cpp) -> the
    texture-space uv differentials for MIPMap::Lookup.

    Intersect the two pixel-offset rays with the tangent plane at the
    hit, take dpdx/dpdy, and solve the 2x2 least-squares for duv/dx and
    duv/dy against the triangle's uv-parameterization derivatives
    (dev["tri_difT"], built at compile). Returns (R, 4) stacked
    [dudx, dvdx, dudy, dvdy], 0 where undefined (level-0 fallback) —
    the full anisotropic footprint the EWA-class imagemap filter
    (texture_eval.py) needs; isotropic consumers take the row max."""
    prim = jnp.maximum(it_prim, 0)
    rows = jnp.take(dev["tri_difT"], prim, axis=1)  # (8, R)
    dpdu = jnp.moveaxis(rows[0:3], 0, -1)
    dpdv = jnp.moveaxis(rows[3:6], 0, -1)
    n = ng
    denom0 = dot(d, n)

    def plane_hit(do_, dd_):
        d_off = d + dd_
        o_off = o + do_
        den = dot(d_off, n)
        t = dot(p_hit - o_off, n) / jnp.where(jnp.abs(den) < 1e-9, 1.0, den)
        return o_off + t[..., None] * d_off - p_hit

    dpdx = plane_hit(dox, ddx)
    dpdy = plane_hit(doy, ddy)
    a00 = dot(dpdu, dpdu)
    a01 = dot(dpdu, dpdv)
    a11 = dot(dpdv, dpdv)
    det = a00 * a11 - a01 * a01
    ok = (jnp.abs(det) > 1e-18) & (jnp.abs(denom0) > 1e-9)
    inv = 1.0 / jnp.where(ok, det, 1.0)

    def solve(dp):
        b0 = dot(dp, dpdu)
        b1 = dot(dp, dpdv)
        du = (a11 * b0 - a01 * b1) * inv
        dv = (a00 * b1 - a01 * b0) * inv
        return du, dv

    dudx, dvdx = solve(dpdx)
    dudy, dvdy = solve(dpdy)
    duv = jnp.stack([dudx, dvdx, dudy, dvdy], axis=-1)
    good = (ok & jnp.all(jnp.isfinite(duv), axis=-1))[..., None]
    # clamp insane footprints (grazing angles): beyond half the texture
    # the coarsest level is right anyway
    return jnp.where(good, jnp.clip(duv, -0.5, 0.5), 0.0)


def textured_mat(
    dev, mid, uv, p, tex_eval, tex_used, width=None, u_mix=None
) -> "bxdf.MatParams":
    """Material::ComputeScatteringFunctions' texture evaluation step
    (material.cpp): gather the constant-folded parameter table, then
    overwrite each slot that carries a texture id with its compiled
    evaluator's value at (uv, p). tex_used is a STATIC set — untextured
    slots cost nothing at trace time. u_mix resolves mix-material lanes
    to one sub-material (bxdf.resolve_mix) before the gather."""
    mid = bxdf.resolve_mix(dev["mat"], mid, u_mix)
    mp = bxdf.gather_mat(dev["mat"], mid)
    if mp.hz is not None:
        # hair: across-width offset h = -1 + 2*v from the ribbon uv
        # (curve.cpp's flat-curve parameterization)
        h = jnp.clip(-1.0 + 2.0 * uv[..., 1], -0.9995, 0.9995)
        mp = mp._replace(hz=mp.hz._replace(h=h))
    if tex_eval is None or "tex_atlas" not in dev or not tex_used:
        return mp
    mt = dev["mat"]
    atlas = dev["tex_atlas"]

    def ev3(slot, field):
        tid = mt[slot][mid]
        v = tex_eval(atlas, tid, uv, p, width)
        return jnp.where((tid >= 0)[..., None], v, field)

    def ev1(slot, field):
        tid = mt[slot][mid]
        v = jnp.mean(tex_eval(atlas, tid, uv, p, width), axis=-1)
        return jnp.where(tid >= 0, v, field)

    kw = {}
    if "kd" in tex_used:
        kw["kd"] = ev3("kd_tex", mp.kd)
    if "ks" in tex_used:
        kw["ks"] = ev3("ks_tex", mp.ks)
    if "sigma" in tex_used:
        kw["sigma"] = ev1("sigma_tex", mp.sigma)
    if "opacity" in tex_used:
        kw["opacity"] = ev3("opacity_tex", mp.opacity)
    if "rough" in tex_used:
        # roughness feeds the GGX alphas through the remap, so the
        # override recomputes ax/ay (gather_mat's derivation)
        tid = mt["rough_tex"][mid]
        r = jnp.mean(tex_eval(atlas, tid, uv, p, width), axis=-1)
        remap = mt["remap"][mid]
        a_t = jnp.where(
            remap > 0, bxdf.tr_roughness_to_alpha(r), jnp.maximum(r, 1e-3)
        )
        kw["ax"] = jnp.where(tid >= 0, a_t, mp.ax)
        kw["ay"] = jnp.where(tid >= 0, a_t, mp.ay)
        # rough_raw gates the rough-glass lobes (_is_rough_glass): a
        # roughness texture on glass must activate them too
        kw["rough_raw"] = jnp.where(tid >= 0, r, mp.rough_raw)
    return mp._replace(**kw)


def estimate_direct(
    dev, light_distr, it: Interaction, mp, px, py, s, bounce,
    light_idx=None, salt_extra=0, vis_segments=1, sampler=("random", 1),
):
    """pbrt EstimateDirect with MIS, light-sampling half + BSDF-sampling
    half. Traces one shadow ray and (for the BSDF half) one MIS ray.

    light_idx None -> UniformSampleOneLight semantics (random light, pick
    pmf folded into the pdf). light_idx (R,) -> EstimateDirect against that
    specific light (UniformSampleAllLights loops this over every light).
    vis_segments > 1 makes the shadow walk pass through MAT_NONE container
    geometry (see unoccluded_tr). Returns (R,3) direct radiance."""
    with jax.named_scope(ph.SHADE_NEE):  # its traces open deeper scopes
        return _estimate_direct(
            dev, light_distr, it, mp, px, py, s, bounce, light_idx,
            salt_extra, vis_segments, sampler,
        )


def _estimate_direct(
    dev, light_distr, it: Interaction, mp, px, py, s, bounce, light_idx,
    salt_extra, vis_segments, sampler,
):
    salt = bounce * DIMS_PER_BOUNCE + salt_extra

    skind, spp = sampler
    # ---- light-sampling half -------------------------------------------
    u_pick = sample_1d(skind, spp, px, py, s, salt + DIM_LIGHT_PICK)
    u1, u2 = sample_2d(skind, spp, px, py, s, salt + DIM_LIGHT_UV)
    if light_idx is None:
        ls = ld.sample_one_light(dev, light_distr, it.p, u_pick, u1, u2)
    else:
        ls = ld.sample_light_rows(dev, light_idx, it.p, u1, u2)
    wi_l = to_local(ls.wi, it.ss, it.ts, it.ns)
    wo_l = to_local(it.wo, it.ss, it.ts, it.ns)
    f, bsdf_pdf = bxdf.bsdf_eval(mp, wo_l, wi_l)
    f = f * jnp.abs(dot(ls.wi, it.ns))[..., None]
    do_light = it.valid & (ls.pdf > 0.0) & (jnp.max(f, axis=-1) > 0.0) & (
        jnp.max(ls.li, axis=-1) > 0.0
    )
    # shadow ray
    o_s = offset_ray_origin(it.p, it.ng, ls.wi)
    visible, _ = unoccluded_tr(
        dev, o_s, ls.wi, jnp.where(do_light, ls.dist, -1.0), None,
        px, py, s, salt + DIM_LIGHT_UV + 300, segments=vis_segments,
    )
    vis = do_light & visible
    w_light = jnp.where(ls.is_delta, 1.0, power_heuristic(1.0, ls.pdf, 1.0, bsdf_pdf))
    contrib_l = f * ls.li * (w_light / jnp.maximum(ls.pdf, 1e-20))[..., None]
    L = jnp.where(vis[..., None], contrib_l, 0.0)

    # ---- BSDF-sampling half (non-delta lights: area + infinite) ---------
    ul = sample_1d(skind, spp, px, py, s, salt + DIM_BSDF_LOBE + 200)
    ub1, ub2 = sample_2d(skind, spp, px, py, s, salt + DIM_BSDF_UV + 200)
    bs = bxdf.bsdf_sample(mp, wo_l, ul, ub1, ub2)
    wi_w = to_world(bs.wi, it.ss, it.ts, it.ns)
    f_b = bs.f * jnp.abs(dot(wi_w, it.ns))[..., None]
    do_b = (
        it.valid
        & ~bs.is_specular
        & (bs.pdf > 0.0)
        & (jnp.max(f_b, axis=-1) > 0.0)
    )
    o_b = offset_ray_origin(it.p, it.ng, wi_w)
    hit_b = scene_intersect(dev, o_b, wi_w, jnp.inf)
    hit_light = dev["tri_light"][jnp.maximum(hit_b.prim, 0)]
    hit_emissive = (hit_b.prim >= 0) & (hit_light >= 0)
    # emitted toward us?
    if light_idx is not None:
        # restricted to one light: only count hits on that light's triangle
        hit_emissive = hit_emissive & (hit_light == light_idx)
    it_b = make_interaction(dev, hit_b, o_b, wi_w)
    le_b = ld.emitted_radiance(dev, jnp.where(hit_emissive, hit_light, -1), -wi_w, it_b.ng)
    # pdf of light-sampling this direction (for MIS): pick pmf is included
    # in the one-light case and excluded in the restricted case, matching
    # the pdf convention of the light half above
    lpdf_area = ld.emitted_pdf(
        dev, None if light_idx is not None else light_distr, it.p, it_b.p, hit_light, it_b.ng
    )
    if light_idx is not None:
        n_l = dev["light"]["type"].shape[0]
        lpdf_area = lpdf_area * n_l  # undo the uniform pmf folded by emitted_pdf
    # escaped ray toward the env light
    if "envmap" in dev:
        from tpu_pbrt.scene.compiler import LIGHT_INFINITE

        is_env_row = (
            dev["light"]["type"][jnp.maximum(light_idx, 0)] == LIGHT_INFINITE
            if light_idx is not None
            else None
        )
        le_env = ld.env_lookup(dev, wi_w)
        lpdf_env = ld.infinite_pdf(
            dev, None if light_idx is not None else light_distr, wi_w, ref_p=it.p
        )
        if light_idx is not None:
            lpdf_env = lpdf_env * dev["light"]["type"].shape[0]
        miss = hit_b.prim < 0
        if light_idx is not None:
            miss = miss & is_env_row
        le_b = jnp.where(miss[..., None], le_env, le_b)
        lpdf = jnp.where(miss, lpdf_env, jnp.where(hit_emissive, lpdf_area, 0.0))
        got_light = miss | hit_emissive
    else:
        lpdf = jnp.where(hit_emissive, lpdf_area, 0.0)
        got_light = hit_emissive
    w_b = power_heuristic(1.0, bs.pdf, 1.0, lpdf)
    contrib_b = f_b * le_b * (w_b / jnp.maximum(bs.pdf, 1e-20))[..., None]
    L = L + jnp.where((do_b & got_light & (lpdf > 0.0))[..., None], contrib_b, 0.0)
    return L


class WavefrontIntegrator:
    """Base class: the chunked render loop (SamplerIntegrator::Render)."""

    #: extra rays traced per camera ray inside li() (for the Mray/s meter)
    rays_per_camera_ray: float = 1.0

    #: injected time source (utils/clock.py) for the redispatch backoff
    #: window. Class-level so existing constructors stay untouched; the
    #: load/protocheck harnesses set it to a VirtualClock per instance,
    #: turning the recovery ladder's backoff into a virtual-time advance
    #: instead of a wall sleep. WALL forwards to time.sleep, so unarmed
    #: renders behave byte-identically.
    clock = WALL

    def __init__(self, params, scene, options):
        self.params = params
        self.scene = scene
        self.options = options
        # "uniform" -> None; "power" -> Distribution1D; "spatial" -> the
        # per-voxel SpatialLightDistribution. What was BUILT decides: the
        # compiler says, loudly, where that is not what the file asked for
        # (`scene/light_distribution`; one light keeps power and loses nothing)
        strategy = scene.light_strategy_built
        if strategy == "uniform":
            self.light_distr = None
        elif strategy == "spatial":
            self.light_distr = scene.spatial_distr
        else:
            self.light_distr = scene.light_distr
        # shadow rays must pass through MAT_NONE container geometry (pbrt
        # VisibilityTester); pay the multi-segment walk only when the scene
        # actually has null interfaces
        self.vis_segments = 4 if scene.has_null_materials else 1
        # compiled texture evaluator (None when everything constant-folded)
        self.tex_eval = getattr(scene, "tex_eval", None)
        self.tex_used = getattr(scene, "tex_used", frozenset())
        # sampler plugin dispatch (VERDICT r3 #7): the scene file's
        # Sampler directive selects the per-dimension stream structure
        self.skind = normalize_sampler_name(scene.sampler.name)
        self.spp = int(scene.sampler.spp)
        self._prepare_sampler()

    def _prepare_sampler(self):
        """Bind the sobol sampler's pixel-grid log2 for THIS scene onto
        the integrator (self._sobol_m — static per scene, threaded
        explicitly into every traced body; ADVICE r4 retired the old
        module-global context). Also downgrades to the (0,2) sampler
        when spp * 4^m would overflow the int32 global index (sobol.cpp
        uses 64-bit here)."""
        self._sobol_m = 0
        if self.skind != "sobol":
            return
        from tpu_pbrt.core.sampling import sobol_resolution_log2

        m = sobol_resolution_log2(self.scene.film.full_resolution)
        self._sobol_m = m
        if self.spp << (2 * m) >= (1 << 31):
            from tpu_pbrt.utils.error import Warning as _W

            _W(
                "sobol: spp * 4^ceil(log2(res)) exceeds the 32-bit global "
                "index range; SUBSTITUTING the (0,2)-sequence sampler"
            )
            self.skind = "02"

    def u1d(self, px, py, s, salt):
        return sample_1d(self.skind, self.spp, px, py, s, salt)

    def u2d(self, px, py, s, salt):
        return sample_2d(self.skind, self.spp, px, py, s, salt)

    def _regen_enabled(self) -> bool:
        """Whether this integrator opts into the persistent-wavefront
        in-place-regeneration render path (PathIntegrator overrides;
        everything else keeps the fixed-batch chunk loop)."""
        return False

    def film_jitter(self, px, py, s):
        """In-pixel film sample offset for sample s of pixel (px, py) —
        a pure function of the work item, so the pool renderer can
        recompute it at deposit time instead of carrying it."""
        if self.skind == "sobol":
            # true SobolSampler film dims: the global index remap
            # guarantees sample s of pixel p lands inside p; dims
            # 0/1 give the in-pixel offset (sobol.cpp)
            from tpu_pbrt.core.sampling import (
                _sobol_raw_bits,
                sobol_interval_to_index,
            )

            m_res = self._sobol_m
            gi = sobol_interval_to_index(m_res, s, px, py)
            sc = jnp.float32((1 << m_res) * 2.3283064365386963e-10)
            fx = jnp.clip(
                _sobol_raw_bits(gi, 0).astype(jnp.uint32).astype(jnp.float32)
                * sc - px.astype(jnp.float32), 0.0, 0.9999999)
            fy = jnp.clip(
                _sobol_raw_bits(gi, 1).astype(jnp.uint32).astype(jnp.float32)
                * sc - py.astype(jnp.float32), 0.0, 0.9999999)
            return fx, fy
        # film sample: per-pixel scrambled (0,2)-sequence
        sx_scr = hash_u32(px, py, 0x11)
        sy_scr = hash_u32(px, py, 0x22)
        return sobol_2d(s, sx_scr, sy_scr)

    def work_to_rays(self, cam, spp, x0, y0, w, npix, start_pix, start_s, k):
        """Flat work offsets k (R,) -> camera rays.

        The global work index (pix*spp + sample) can exceed int32 at
        production spp, so the range start is carried as (start_pix,
        start_s) and the arithmetic stays within int32. Shared by the
        fixed-batch chunk body and the pool renderer's regeneration step
        — both derive the SAME (px, py, s) and sampler streams for a
        given work item, which is what makes the two modes produce the
        same estimator."""
        s_tot = start_s + k
        pix = start_pix + s_tot // spp
        s = s_tot % spp
        valid = pix < npix
        px = x0 + pix % w
        py = y0 + pix // w
        fx, fy = self.film_jitter(px, py, s)
        p_film = jnp.stack(
            [px.astype(jnp.float32) + fx, py.astype(jnp.float32) + fy],
            axis=-1,
        )
        u_lens = jnp.stack(list(self.u2d(px, py, s, DIM_LENS)), axis=-1)
        o, d, wt = generate_rays(cam, p_film, u_lens)
        return valid, px, py, s, p_film, o, d, wt

    def mat_at(self, dev, it, width=None, u_mix=None) -> "bxdf.MatParams":
        """Textured material parameters at a surface interaction; width
        is the optional (R, 4) texture-space ray-differential footprint
        (camera hits) driving EWA/trilinear mip selection; u_mix the
        optional mix-material selection draw (bxdf.resolve_mix)."""
        return textured_mat(
            dev, it.mat, it.uv, it.p, self.tex_eval, self.tex_used, width,
            u_mix,
        )

    # -- subclass hook ----------------------------------------------------
    def li(self, dev, o, d, px, py, s):
        raise NotImplementedError

    # -- chunk-plan preparation (the submit/step seam) --------------------
    def prepare_chunks(
        self, scene=None, mesh=None, chunk: Optional[int] = None,
    ) -> ChunkPlan:
        """Build (or re-use, via the single-slot jit cache) the chunk
        decomposition + jitted dispatch closure for rendering ``scene``
        on ``mesh``. ``chunk`` overrides the platform-default chunk size
        — the render service passes its slice width here so one
        submit/step quantum stays small enough to preempt between.

        Pure preparation: nothing is dispatched. Repeat calls with the
        same (scene, mesh, chunk, knobs) return a plan sharing the SAME
        compiled closure — the 0-recompile contract the jaxpr audit and
        the service's warm-resubmit criterion both pin."""
        COMPILES.install()  # dispatch() tells a compile refusal by it
        scene = scene or self.scene
        if mesh is None and getattr(self.options, "mesh_shape", None):
            from tpu_pbrt.parallel.mesh import resolve_mesh

            mesh = resolve_mesh(self.options.mesh_shape)
        film = scene.film
        cam = scene.camera
        dev = scene.dev
        x0, x1, y0, y1 = film.sample_bounds()
        w = x1 - x0
        h = y1 - y0
        npix = w * h
        spp = scene.sampler.spp
        total = npix * spp
        n_dev = 1 if mesh is None else mesh.devices.size

        # Default chunk: the stream tracer's sort/compaction steps amortize
        # over BIG waves, so TPU dispatches carry 1M camera rays (the
        # MAX_RAYS_PER_DISPATCH cap in accel/traverse.py applies to the
        # legacy unrolled walkers, not the stream worklist). The legacy
        # per-ray walkers (TPU_PBRT_BVH=packet|wide|binary) are orders of
        # magnitude slower on divergent waves and keep short 8k
        # dispatches. CPU (tests) prefers smaller programs to bound
        # compile time.
        is_tpu = jax.devices()[0].platform != "cpu"
        if is_tpu:
            default_chunk = (1 << 20) if cfg.bvh == "stream" else (1 << 13)
        else:
            default_chunk = min(MAX_RAYS_PER_DISPATCH >> 1, 1 << 17)
        if chunk is None:
            chunk = int(cfg.chunk if cfg.chunk is not None else default_chunk)
        chunk = int(chunk)
        chunk = min(chunk, max(1024 * n_dev, total))
        chunk = max((chunk // n_dev) * n_dev, n_dev)
        per_dev = chunk // n_dev
        n_chunks = (total + chunk - 1) // chunk

        # Persistent wavefront (ISSUE 1): integrators that opt in drain
        # each chunk's work range through a resident pool of path slots
        # (camera-ray regeneration in place, PathIntegrator.pool_chunk)
        # instead of advancing one fixed batch to max_depth. The pool is
        # ~1/4 of the per-device work range so regeneration has material
        # to refill from; TPU_PBRT_POOL overrides, TPU_PBRT_REGEN=0
        # disables (A/B against the fixed-batch loop).
        use_regen = self._regen_enabled()
        pool = 0
        if use_regen:
            pool = int(cfg.pool)
            if pool <= 0:
                pool = max(per_dev // 4, min(per_dev, 4096))
            pool = min(pool, per_dev)

        # who gets what on a mesh (parallel/mesh.py): granules of g work
        # items dealt round-robin; one device keeps consecutive items
        from tpu_pbrt.parallel.mesh import work_granule, work_item

        g = work_granule(per_dev, spp, n_dev)

        def dealt(k):
            """A device's local work counter -> offset from its start."""
            return work_item(k, 0, n_dev, g)

        def body(dev, start_pix, start_s, n_rays_in_body):
            """Film contribution of this device's n work items from
            start on — a pure function of the work range (idempotent:
            the checkpoint/re-dispatch unit, SURVEY.md §5.3/5.4)."""
            k = dealt(jnp.arange(n_rays_in_body, dtype=jnp.int32))
            valid, px, py, s, p_film, o, d, wt = self.work_to_rays(
                cam, spp, x0, y0, w, npix, start_pix, start_s, k
            )
            out = self.li(dev, o, d, px, py, s)
            if len(out) == 4:
                # splat-producing integrator (BDPT t=1 / MLT / SPPM):
                # (L, nrays, splat_xy (R,K,2), splat_val (R,K,3))
                L, nrays, sxy, sval = out
                sval = jnp.where(valid[..., None, None], sval, 0.0)
                splats = (sxy.reshape(-1, 2), sval.reshape(-1, 3))
            else:
                L, nrays = out
                splats = None
            nrays = jnp.sum(jnp.where(valid, nrays, 0))
            p_film = jnp.where(valid[..., None], p_film, -1e6)  # lands outside crop
            return p_film, L, wt, nrays, splats

        def split_start(g0):
            """Global work index (python int, unbounded) -> int32 pair."""
            return g0 // spp, g0 % spp

        # A fresh jax.jit closure recompiles on every render() call; cache
        # the jitted chunk function across calls (single slot, keyed on the
        # scene object identity + static loop parameters) so repeat renders
        # of the same scene — bench warmup, spp-chunked loops, resumed
        # checkpoints, warm service resubmits — hit the compile cache. The
        # cache holds a strong ref to the scene, keeping the keyed identity
        # stable.
        # the telemetry kill switch changes the traced program (counter
        # carry present/absent), so it is part of the closure identity —
        # a reload() between renders must not reuse the stale closure
        from tpu_pbrt.chaos import CHAOS
        from tpu_pbrt.obs import counters as _obs_counters

        # chaos nan:wave injection threads a traced wave index into the
        # single-device pool drain (-1 = clean); its PRESENCE is static
        # program shape, so it is part of the closure identity
        chaos_nan = CHAOS.has_nan() and use_regen and mesh is None
        # in-flight window depth this plan compiles for (ISSUE 13).
        # Depth 1 donates the film carry — in-place accumulation, the
        # exact pre-pipeline program. Depth > 1 compiles WITHOUT
        # donation: re-donating a chained carry (the previous
        # dispatch's donation-aliased output) trips XLA:CPU's
        # synchronous donation path and the whole chunk executes INLINE
        # in the dispatch call (measured: dispatch ~58 ms..3.7 s,
        # block_until_ready ~0 — the overlap the window exists to
        # create silently erased), and an un-donated carry is also what
        # lets a deferred checkpoint write hold the previous
        # accumulator while newer slices are in flight. The price is
        # one extra film allocation per in-flight slice;
        # TPU_PBRT_PIPELINE=1 restores the zero-copy chain. Donation
        # changes the compiled program, so it is part of the closure
        # identity.
        from tpu_pbrt.parallel.mesh import resolve_pipeline_depth

        pipe_depth = resolve_pipeline_depth(mesh)
        donate = (0,) if pipe_depth == 1 else ()
        jit_key = (
            scene, mesh, chunk, spp, total, n_dev, pool, use_regen,
            _obs_counters.enabled(), CHAOS.trace_key(), bool(donate),
        )
        cached = getattr(self, "_jit_cache", None)
        if cached is not None and all(
            a is b if i < 2 else a == b for i, (a, b) in enumerate(zip(cached[0], jit_key))
        ):
            jfn = cached[1]
        else:
            if use_regen and mesh is None:
                if chaos_nan:

                    def chunk_fn(
                        state: FilmState, dev, start_pix, start_s, nanw
                    ):
                        fs2, nrays, live, waves, trunc, ctr = self.pool_chunk(
                            dev, state, start_pix, start_s, chunk, pool,
                            film=film, cam=cam, nan_wave=nanw,
                        )
                        return fs2, (nrays, live, waves, trunc, ctr)

                else:

                    def chunk_fn(state: FilmState, dev, start_pix, start_s):
                        fs2, nrays, live, waves, trunc, ctr = self.pool_chunk(
                            dev, state, start_pix, start_s, chunk, pool,
                            film=film, cam=cam,
                        )
                        # ctr is None under TPU_PBRT_TELEMETRY=0 — an
                        # empty pytree leaf, so the killed program is
                        # unchanged
                        return fs2, (nrays, live, waves, trunc, ctr)

            elif use_regen:
                from tpu_pbrt.parallel.mesh import (
                    device_spread,
                    sharded_pool_renderer,
                )

                def per_device_fn(dev, start):
                    # each device drains ITS share of the dispatch (per_dev
                    # items, every n_dev-th granule from start on) with its
                    # own resident pool and work counter (see
                    # sharded_pool_renderer for the lockstep-freedom
                    # contract)
                    fs2, nrays, live, waves, trunc, ctr = self.pool_chunk(
                        dev, film.init_state(), start[0, 0], start[0, 1],
                        per_dev, pool, film=film, cam=cam,
                        work_offset=dealt,
                    )
                    # the one-hot (waves, rays) block rides the aux psum
                    # out as the per-device wave and ray spread (ROADMAP
                    # multi-chip metric); None when telemetry is killed
                    spread = (
                        device_spread((waves, nrays), n_dev)
                        if ctr is not None else None
                    )
                    return fs2, (nrays, live, waves, trunc, ctr, spread)

                step = sharded_pool_renderer(mesh, per_device_fn)

                def chunk_fn(state: FilmState, dev, starts):
                    contrib, aux = step(dev, starts)
                    from tpu_pbrt.core.film import merge_film

                    return merge_film(state, contrib), aux

            elif mesh is None:
                # pixel-major chunks that tile the frame exactly take the
                # film's scatter-free aligned accumulation path
                aligned = film.aligned_chunk_pixels(chunk, spp) > 0

                def chunk_fn(state: FilmState, dev, start_pix, start_s):
                    p_film, L, wt, nrays, splats = body(dev, start_pix, start_s, chunk)
                    nf = _fixed_batch_nonfinite(p_film, L)
                    if aligned:
                        state = film.add_samples_aligned(
                            state, start_pix, spp, p_film, L, wt
                        )
                    else:
                        state = film.add_samples(state, p_film, L, wt)
                    if splats is not None:
                        state = film.add_splats(state, *splats)
                    return state, (nrays if nf is None else (nrays, nf))

            else:
                from tpu_pbrt.parallel.mesh import sharded_chunk_renderer

                def per_device_fn(dev, start):
                    # start: this device's (1, 2) shard of the (n_dev, 2) pairs
                    p_film, L, wt, nrays, splats = body(dev, start[0, 0], start[0, 1], per_dev)
                    nf = _fixed_batch_nonfinite(p_film, L)
                    contrib = film.add_samples(film.init_state(), p_film, L, wt)
                    if splats is not None:
                        contrib = film.add_splats(contrib, *splats)
                    return contrib, (nrays if nf is None else (nrays, nf))

                step = sharded_chunk_renderer(mesh, per_device_fn)

                def chunk_fn(state: FilmState, dev, starts):
                    contrib, aux = step(dev, starts)
                    from tpu_pbrt.core.film import merge_film

                    return merge_film(state, contrib), aux

            build_chunk = chunk_fn

            def chunk_fn(*args):  # noqa: F811 — the name XLA shows
                with jax.named_scope(ph.CHUNK):
                    return build_chunk(*args)

            jfn = jax.jit(chunk_fn, donate_argnums=donate)
            self._jit_cache = (jit_key, jfn)

        # start cursors move host->device once per plan; the transfer is
        # EXPLICIT (device_put) so the whole loop runs clean under
        # jax.transfer_guard("disallow") — the jaxpr audit's smoke render
        if mesh is None:
            starts = [
                tuple(
                    jax.device_put(np.int32(v))
                    for v in split_start(c * chunk)
                )
                for c in range(n_chunks)
            ]
        else:
            starts = []
            for c in range(n_chunks):
                pairs = [
                    split_start(c * chunk + work_item(0, i, n_dev, g))
                    for i in range(n_dev)
                ]
                starts.append(
                    jax.device_put(np.asarray(pairs, np.int32))
                )  # (n_dev, 2)

        fp = render_fingerprint(chunk=chunk, spp=spp, total=total, scene=scene)
        return ChunkPlan(
            integrator=self, scene=scene, mesh=mesh, film=film, cam=cam,
            chunk=chunk, per_dev=per_dev, n_dev=n_dev, n_chunks=n_chunks,
            spp=spp, total=total, npix=npix, bounds=(x0, x1, y0, y1),
            pool=pool, use_regen=use_regen, chaos_nan=chaos_nan,
            starts=starts, jfn=jfn, fingerprint=fp,
            pipeline_depth=pipe_depth,
        )

    # -- the loop ---------------------------------------------------------
    def render(
        self, scene=None, mesh=None, checkpoint_path=None, checkpoint_every=0,
        max_seconds: float = 0.0,
    ) -> RenderResult:
        """The SamplerIntegrator::Render loop. mesh=None runs single-device;
        a jax.sharding.Mesh runs the SPMD tile scheduler (parallel/mesh.py):
        work indices round-robined across devices, film merged by psum.

        max_seconds > 0 time-boxes the loop: after the budget elapses the
        loop stops at a chunk boundary and returns a partial render with
        completed_fraction < 1. NOTE the work domain is pixel-major, so a
        partial film is spatially truncated (trailing pixels unsampled) —
        only valid for throughput measurement or checkpointed resume, not
        for image comparison. The throughput meter stays valid — it
        divides rays actually traced by wall time. The stop can overshoot
        the budget by a few in-flight chunk durations (the sync lags the
        dispatch to keep the pipe full)."""
        from tpu_pbrt.obs.trace import TRACE

        with TRACE.span("render/prepare_chunks") as sp, COMPILES.stages_into(sp):
            plan = self.prepare_chunks(scene, mesh)
        scene, mesh, film = plan.scene, plan.mesh, plan.film
        spp, total = plan.spp, plan.total
        n_chunks, pool = plan.n_chunks, plan.pool
        use_regen = plan.use_regen

        # -- checkpoint/resume (SURVEY.md §5.4): film accumulation is
        # associative and chunks are idempotent, so a checkpoint is just
        # (film state, chunk cursor); the counter-based RNG makes resumed
        # renders bit-identical to uninterrupted ones.
        from tpu_pbrt.utils.stats import STATS, ProgressReporter

        self._prepare_sampler()
        ckpt_path = checkpoint_path or getattr(self.options, "checkpoint_path", None)
        checkpoint_every = checkpoint_every or getattr(self.options, "checkpoint_every", 0)
        first_chunk = 0
        prev_rays = 0
        prev_ctr: Dict[str, Any] = {}
        fp = plan.fingerprint
        with TRACE.span("render/init_state"):  # the film's accumulator, or a checkpoint's
            state = film.init_state()
            if ckpt_path and checkpoint_exists(ckpt_path):
                state, first_chunk, prev_rays, prev_ctr = load_checkpoint(
                    ckpt_path, fp
                )

        from tpu_pbrt.chaos import CHAOS
        from tpu_pbrt.obs import counters as obs_counters
        from tpu_pbrt.obs.flight import FLIGHT
        from tpu_pbrt.obs.metrics import METRICS, phase_histogram

        # per-phase wall-time attribution (ISSUE 10 / ROADMAP #1 stage
        # two): dispatch vs device-wait vs deposit-develop vs checkpoint,
        # observed into the process-wide phase histogram. Host-side
        # only: each region is timed ONCE, by its TRACE span, whose
        # duration is fed here; with TPU_PBRT_METRICS=0 nothing is
        # recorded or reported at all.
        metrics_on = METRICS.enabled
        phase_s: Dict[str, float] = {}

        def _phase(name: str, dt: float) -> None:
            if not metrics_on:
                return
            phase_s[name] = phase_s.get(name, 0.0) + dt
            phase_histogram().observe(dt, phase=name)

        # pre-render stream-capacity audit (fails loudly on a worklist
        # overflow — see ChunkPlan.capacity_audit)
        plan.capacity_audit()

        quiet = bool(getattr(self.options, "quiet", False))
        progress = ProgressReporter(n_chunks, "Rendering", quiet=quiet)
        ray_counts = []
        occ_counts = []  # regen mode: (live lane-waves, waves) per chunk
        ctr_counts = []  # telemetry: per-chunk WaveCounters (device side)
        spread_counts = []  # telemetry (mesh): per-device (waves, rays) blocks
        nf_counts = []  # fixed-batch firewall: per-chunk scrub counts
        # host-side recovery accounting (ISSUE 5): flows into the obs
        # counter dict, the flight recorder and RenderResult.stats
        recovery = {
            "redispatches": 0,
            "rollbacks": 0,
            "restarts": 0,
            "nonfinite_retries": 0,
            "backoff_ms": 0,
        }
        # retry extras the INITIAL resume brought in from prior
        # processes: an in-process rollback later reloads a snapshot
        # this very loop wrote, so prev_ctr then already bakes in part
        # of `recovery` — ctr_snapshot must add only the unbaked delta
        # (prev_ctr[key] - prior_rec[key] is this process's baked share)
        # or every rollback would double-count the extras it replays
        prior_rec = {
            k: int(prev_ctr.get(k, 0))
            for k in ("chunks_redispatched", "retry_backoff_ms")
        }

        def ctr_snapshot(n_ctr=None, n_nf=None, rec=None):
            """Cumulative host counter dict (checkpoint payload / final
            stats): the saved snapshot + everything fetched so far. The
            device_get inside to_host is the telemetry's one explicit
            drain-boundary fetch (checkpoint writes are drain
            boundaries too). Folds in the fixed-batch firewall counts
            and the host-side retry/backoff accounting. n_ctr/n_nf/rec
            restrict the snapshot to a LIST PREFIX + a recovery-dict
            copy captured when a deferred (pipelined) checkpoint was
            enqueued — the written counters cover exactly the chunks
            the snapshot's cursor covers, not the slices dispatched
            ahead of it."""
            snap = obs_counters.merge_host(
                prev_ctr, obs_counters.to_host(ctr_counts[:n_ctr])
            )
            nf = nf_counts[:n_nf]
            if nf:
                snap = obs_counters.merge_host(
                    snap,
                    {
                        "nonfinite_deposits": sum(
                            int(v) for v in jax.device_get(nf)
                        )
                    },
                )
            rec = recovery if rec is None else rec
            extra = {}
            for key, cur in (
                ("chunks_redispatched", rec["redispatches"]),
                ("retry_backoff_ms", rec["backoff_ms"]),
            ):
                # clamp: a rollback that fell back to a PRIOR process's
                # .prev can hold smaller extras than the initial resume
                baked = max(0, int(snap.get(key, 0)) - prior_rec[key])
                if cur > baked:
                    extra[key] = cur - baked
            return obs_counters.merge_host(snap, extra)

        chunks_done = first_chunk
        FLIGHT.heartbeat(
            "render", chunks=n_chunks, resumed_at=first_chunk, spp=spp,
        )
        # heartbeat cadence: bounded line count on long renders, but
        # every chunk on short ones so the flight timeline has substance
        hb_every = max(1, n_chunks // 16)
        # -- recovery policy (ISSUE 5): capped exponential backoff with
        # deterministic jitter between re-dispatches, an attempt budget
        # AND a wall-clock deadline (a tight retry loop against a hung
        # backend must not burn the whole capture), and a final
        # emergency checkpoint before giving up so completed work is
        # never lost.
        retry_max = int(cfg.retry_max)
        retry_deadline = float(cfg.retry_deadline)
        firewall_mode = cfg.nonfinite  # scrub | raise | retry
        if firewall_mode != "scrub" and not obs_counters.enabled():
            # the strict modes read the firewall's scrub COUNT, which
            # rides the telemetry counters — with them killed the check
            # would silently degrade to scrub mode, the exact silent
            # contamination raise/retry exist to prevent
            raise ValueError(
                f"TPU_PBRT_NONFINITE={firewall_mode} needs the telemetry "
                "counters (the firewall's scrub count), but "
                "TPU_PBRT_TELEMETRY=0 disabled them; re-enable telemetry "
                "or use the default scrub mode"
            )

        def chunk_nonfinite(aux):
            """The per-chunk firewall scrub count (device scalar), or
            None when telemetry is off (nothing to check)."""
            if use_regen:
                ctr = aux[4] if len(aux) > 4 else None
                return None if ctr is None else ctr.nonfinite
            return aux[1] if isinstance(aux, tuple) else None

        t0 = time.time()
        c = first_chunk
        attempt = 0
        #: COMPILES.programs once the first dispatch has returned: the
        #: chunk loop must build or load nothing after it
        programs_0 = None
        retry_t0 = None  # wall clock of the current failure streak
        timed_out = False
        # -- in-flight dispatch window (ISSUE 13): keep `depth` chunk-
        # slices launched ahead and retire the oldest only when the
        # window is full, so every piece of host-side work below —
        # progress/heartbeats, deposit bookkeeping, deferred checkpoint
        # serialization — runs under the device compute of the slices
        # still in flight. Counters and device_get fetches still
        # reconcile only at the existing drain boundaries. The depth
        # comes from the PLAN (not re-resolved here): donation is
        # compiled into the closure, and the loop's hold-the-carry
        # checkpoint deferral is only legal against the depth the
        # closure was built for.
        from tpu_pbrt.parallel.checkpoint import begin_host_copy

        depth = plan.pipeline_depth
        window = DispatchWindow(
            depth,
            on_wait=lambda dt: _phase("device_wait", dt),
            span_name="render/chunk_retire",
        )
        # tpu-scope: one trace context for the whole render request —
        # every in-flight chunk-slice becomes an async span under it,
        # causally bound dispatch->retire by a flow event, so a depth-N
        # trace renders as N overlapping tracks instead of flat X spans
        # that pretend the loop is serial
        rloop_tid = TRACE.trace_id("render")

        def _write_checkpoint(st, cursor, n_ray, n_ctr, n_nf, rec=None):
            """One durable cadence write: chunks [0, cursor) of `st`,
            counters restricted to the captured list prefixes."""
            with TRACE.span("render/checkpoint", chunk=cursor) as sp:
                save_checkpoint(
                    ckpt_path, st, cursor,
                    prev_rays + sum(
                        int(r)
                        for r in jax.device_get(ray_counts[:n_ray])
                    ),
                    fingerprint=fp,
                    counters=ctr_snapshot(n_ctr, n_nf, rec),
                )
            _phase("checkpoint", sp.seconds)

        def _queue_checkpoint(cursor):
            """Cadence checkpoint at `cursor`. With slices in flight the
            durable write is deferred to the cursor's retirement — the
            npz compression + CRC + fsync then run under the compute of
            the newer slices. At depth > 1 the carry is never donated
            (plan.pipeline_depth compiled donation out), so the
            deferred write simply HOLDS the live accumulator reference
            and starts its device->host copy early. With an empty
            window (depth 1, or the first chunk) write immediately:
            the exact pre-pipeline path."""
            lens = (len(ray_counts), len(ctr_counts), len(nf_counts))
            if not len(window):
                _write_checkpoint(state, cursor, *lens)
                return
            snap = state
            begin_host_copy(snap)
            rec = dict(recovery)
            window.defer(
                cursor,
                lambda: _write_checkpoint(snap, cursor, *lens, rec=rec),
            )

        with STATS.phase("Integrator/Render loop"):
            while c < n_chunks or len(window):
                try:
                    if c < n_chunks:
                        # failure seam (SURVEY.md §2e worker-failure row):
                        # a dispatch that dies is re-run — chunks are
                        # idempotent pure functions of the work range, so
                        # re-dispatch is exact. If the failure could have
                        # poisoned the accumulated film (a mid-flight
                        # device loss), the checkpoint (if enabled) rolls
                        # the loop back to the last durable state instead.
                        # The CHAOS registry (tpu_pbrt/chaos) injects
                        # deterministic failures here — the promoted form
                        # of the old test-only `_fault_hook` monkeypatch.
                        CHAOS.dispatch(c, attempt, mesh=mesh is not None)
                        try:
                            # the first dispatch blocks the host on jit
                            # trace+compile; later ones are async enqueues
                            # — and one issued with older slices still in
                            # flight has its host cost hidden under their
                            # compute, so it is attributed separately
                            # (dispatch_ahead)
                            if c == first_chunk:
                                ph_name = "dispatch_compile"
                                span = "render/chunk_dispatch+compile"
                            elif len(window):
                                ph_name = "dispatch_ahead"
                                span = "render/chunk_dispatch_ahead"
                            else:
                                ph_name = "dispatch"
                                span = "render/chunk_dispatch"
                            with TRACE.span(span, chunk=c) as sp, COMPILES.stages_into(sp):
                                state, aux = plan.dispatch(state, c)
                            _phase(ph_name, sp.seconds)
                        except jax.errors.JaxRuntimeError as e:
                            # real device/runtime loss mid-dispatch: the
                            # donated film accumulator can no longer be
                            # trusted — route through the poisoning
                            # recovery (checkpoint rollback or restart),
                            # never reuse `state`
                            raise ChunkDispatchError(
                                f"device dispatch failed: {e}",
                                poisons_state=True,
                            ) from e
                        if firewall_mode != "scrub":
                            # strict firewall: check THIS chunk's scrub
                            # count (costs one per-chunk device sync —
                            # opt-in; resolve_pipeline_depth forces the
                            # window to depth 1 in these modes, exactly
                            # because of this sync). raise-mode aborts;
                            # retry-mode treats the chunk as poisoned
                            # (its deposits hold zeroed radiance) and
                            # re-renders it exactly.
                            nf_dev = chunk_nonfinite(aux)
                            nf_ct = (
                                0 if nf_dev is None
                                else int(jax.device_get(nf_dev))
                            )
                            if nf_ct:
                                if firewall_mode == "raise":
                                    raise NonFiniteRadianceError(
                                        f"chunk {c} deposited {nf_ct} "
                                        "non-finite radiance sample(s) "
                                        "(scrubbed to zero); "
                                        "TPU_PBRT_NONFINITE=raise treats "
                                        "this as fatal"
                                    )
                                recovery["nonfinite_retries"] += 1
                                raise NonFiniteWaveError(
                                    f"non-finite firewall: chunk {c} "
                                    f"scrubbed {nf_ct} deposit(s)"
                                )
                        attempt = 0
                        retry_t0 = None
                        c += 1
                        if programs_0 is None:
                            programs_0 = COMPILES.programs
                        if use_regen:
                            nrays, lv, wv, trunc = aux[:4]
                            occ_counts.append((lv, wv, trunc))
                            if len(aux) > 4 and aux[4] is not None:
                                ctr_counts.append(aux[4])
                            if len(aux) > 5 and aux[5] is not None:
                                spread_counts.append(aux[5])
                        elif isinstance(aux, tuple):
                            nrays, nf_dep = aux
                            nf_counts.append(nf_dep)
                        else:
                            nrays = aux
                        ray_counts.append(nrays)
                        progress.update()
                        chunks_done = c
                        if c == first_chunk + 1 or c % hb_every == 0:
                            FLIGHT.heartbeat(
                                "render", chunk=c, of=n_chunks,
                                render_s=round(time.time() - t0, 3),
                            )
                        if (
                            ckpt_path and checkpoint_every
                            and c % checkpoint_every == 0
                        ):
                            _queue_checkpoint(c)
                        sid = f"{rloop_tid}/c{c - 1}"
                        TRACE.async_begin(
                            "render/slice", id=sid, cat="slice",
                            chunk=c - 1, trace_id=rloop_tid, span_id=sid,
                        )
                        TRACE.flow_start("slice_flow", id=sid)
                        window.push(c - 1, nrays, span={
                            "name": "render/slice", "id": sid,
                            "cat": "slice", "flow": sid,
                            "trace_id": rloop_tid, "span_id": sid,
                        })
                    # retire the oldest slice(s): only when the window is
                    # full (the host work above ran under their compute),
                    # plus the full drain once the work domain is
                    # exhausted. Each retire blocks on ONE per-chunk sync
                    # handle — the device keeps executing the newer
                    # in-flight slices through the wait.
                    while len(window) and (window.full() or c >= n_chunks):
                        window.retire_one()
                    if max_seconds > 0:
                        # time-boxed mode: the retire above paces the wall
                        # clock to completed work while the window keeps
                        # the pipe full. When the measured chunk rate says
                        # the remaining budget cannot absorb the in-flight
                        # window, drain eagerly — bounding overshoot to
                        # ~1 chunk duration even for very slow chunks.
                        done_n = max(len(ray_counts) - len(window), 1)
                        rate = (time.time() - t0) / done_n
                        if (
                            max_seconds - (time.time() - t0)
                            < (depth + 2) * rate
                        ):
                            window.drain()
                        if time.time() - t0 > max_seconds:
                            timed_out = True
                except ChunkDispatchError as e:
                    # flush the in-flight window BEFORE the ladder: a
                    # poisoning failure discards it outright (rollback/
                    # restart re-renders everything it covered); a clean
                    # failure quiesces it — blocking on the survivors
                    # surfaces any latent async loss here, and the
                    # deferred durable writes land before the retry
                    # streak can burn the attempt budget
                    try:
                        window.flush(discard=e.poisons_state)
                    except ChunkDispatchError as e2:
                        e = e2  # the flush itself found a poisoned device
                        window.flush(discard=True)
                    attempt += 1
                    recovery["redispatches"] += 1
                    STATS.counter("Distribution/Chunks re-dispatched", 1)
                    now = time.time()
                    if retry_t0 is None:
                        retry_t0 = now
                    deadline_hit = (
                        retry_deadline > 0
                        and now - retry_t0 > retry_deadline
                    )
                    if attempt > retry_max or deadline_hit:
                        # unrecoverable: write a final emergency
                        # checkpoint (unless this very failure poisoned
                        # the accumulator — then the last durable file
                        # already holds everything trustworthy) so
                        # completed work survives the crash
                        if ckpt_path and not e.poisons_state:
                            save_checkpoint(
                                ckpt_path, state, c,
                                prev_rays + sum(
                                    int(r)
                                    for r in jax.device_get(ray_counts)
                                ),
                                fingerprint=fp, counters=ctr_snapshot(),
                            )
                            FLIGHT.heartbeat(
                                "render_emergency_checkpoint", chunk=c,
                                attempt=attempt,
                            )
                        reason = (
                            f"retry deadline ({retry_deadline:.0f}s) exceeded"
                            if deadline_hit
                            else f"failed {attempt} times"
                        )
                        raise RuntimeError(f"chunk {c} {reason}") from e
                    if e.poisons_state and ckpt_path and checkpoint_exists(ckpt_path):
                        state, c, prev_rays, prev_ctr = load_checkpoint(
                            ckpt_path, fp
                        )
                        recovery["rollbacks"] += 1
                        ray_counts.clear()
                        occ_counts.clear()
                        ctr_counts.clear()
                        spread_counts.clear()
                        nf_counts.clear()
                    elif e.poisons_state:
                        # no durable state to roll back to: restart the render
                        state = film.init_state()
                        c = 0
                        prev_rays = 0
                        prev_ctr = {}
                        # the prior-process extras restarted with it
                        prior_rec = {k: 0 for k in prior_rec}
                        recovery["restarts"] += 1
                        ray_counts.clear()
                        occ_counts.clear()
                        ctr_counts.clear()
                        spread_counts.clear()
                        nf_counts.clear()
                    backoff_s = redispatch_backoff(c, attempt)
                    recovery["backoff_ms"] += int(backoff_s * 1000)
                    FLIGHT.heartbeat(
                        "render_redispatch", chunk=c, attempt=attempt,
                        poisoned=e.poisons_state,
                        backoff_s=round(backoff_s, 3),
                        backoff_total_ms=recovery["backoff_ms"],
                        error=str(e)[:200],
                    )
                    if backoff_s > 0:
                        # the backoff window's extent is known the
                        # moment it opens — record it as an explicit-
                        # duration span so the trace shows WHY the
                        # timeline has a hole
                        TRACE.complete(
                            "render/backoff", backoff_s * 1e6, chunk=c,
                            attempt=attempt, trace_id=rloop_tid,
                        )
                        self.clock.sleep(backoff_s)
                    continue
                if timed_out:
                    break
            # device execution of the queued wave batches (and, on a
            # mesh, the ICI film psum/merge) completes inside this sync
            with TRACE.span("render/wave_drain+film_merge") as sp:
                jax.block_until_ready(state)
            _phase("device_wait", sp.seconds)
        programs_late = (
            0 if programs_0 is None else COMPILES.programs - programs_0
        )
        secs = time.time() - t0
        progress.done()
        completed_fraction = chunks_done / max(n_chunks, 1)
        rays = prev_rays + int(sum(int(r) for r in jax.device_get(ray_counts)))
        STATS.counter("Integrator/Rays traced", rays)
        STATS.counter("Integrator/Camera rays traced", total)
        STATS.distribution("Integrator/Rays per camera ray", rays / max(total, 1))
        # the drain-boundary counter fetch (the telemetry's ONE
        # device_get for the whole render when no checkpoints fired)
        ctr_total = ctr_snapshot()
        if obs_counters.enabled() and ctr_total:
            FLIGHT.counters(ctr_total, phase="render_done")
        else:
            FLIGHT.heartbeat("render_done", rays=rays, seconds=round(secs, 3))
        if ckpt_path:
            with TRACE.span("render/checkpoint", chunk=chunks_done) as sp:
                save_checkpoint(
                    ckpt_path, state, chunks_done, rays, fingerprint=fp,
                    counters=ctr_total,
                )
            _phase("checkpoint", sp.seconds)
        # pbrt film.cpp WriteImage splatScale: splats (BDPT t=1, MLT, SPPM)
        # are deposited once per SAMPLE, so the developed image divides by
        # the number of samples actually taken — a time-boxed partial
        # render deposited only completed_fraction of them (the rgb plane
        # self-normalizes via its weight sum; the splat plane cannot)
        n_splat_samples = max(spp * completed_fraction, 1e-9)
        with TRACE.span("render/develop") as sp:
            img = film.develop(state, splat_scale=1.0 / n_splat_samples)
        develop_s = sp.seconds
        FLIGHT.heartbeat("develop")
        if film.filename:
            with TRACE.span("render/write_image") as sp:
                try:
                    film.write_image(state, splat_scale=1.0 / n_splat_samples)
                except (OSError, ValueError) as e:
                    # the image IS the render's product: a run that
                    # could not write it has failed, whatever it traced
                    from tpu_pbrt.utils.error import PbrtError

                    raise PbrtError(
                        f"could not write image {film.filename}: {e}"
                    ) from e
            develop_s += sp.seconds
        _phase("deposit_develop", develop_s)
        stats: Dict[str, Any] = {
            # programs built or loaded between the first dispatch's
            # return and the end of the chunk loop (steady state: 0)
            "programs_after_first_chunk": programs_late,
        }
        if any(recovery.values()):
            # the render survived at least one failure — surface the
            # full retry/rollback/backoff accounting next to the image
            stats["recovery"] = dict(recovery)
        if use_regen and occ_counts:
            occ_host = jax.device_get(occ_counts)
            lv_t = sum(int(a) for a, _, _ in occ_host)
            wv_t = sum(int(b) for _, b, _ in occ_host)
            tr_t = sum(int(t) for _, _, t in occ_host)
            if tr_t:
                # the pool's max_waves safety cutoff fired with work still
                # outstanding — a silently darker image must never pass as
                # a completed render
                from tpu_pbrt.utils.error import Warning as _W

                _W(
                    f"persistent wavefront truncated {tr_t} chunk drain(s) "
                    "at the max_waves safety bound; the image is missing "
                    "samples (raise TPU_PBRT_POOL or report a bug)"
                )
                stats["truncated_chunks"] = tr_t
            stats |= {
                # fraction of pool slots holding a LIVE path at trace
                # time, averaged over every wave dispatched (the judged
                # occupancy metric: ~0.3-0.4 for the fixed-batch loop on
                # depth-5 diffuse scenes, near 1.0 with regeneration)
                "mean_wave_occupancy": lv_t / max(wv_t * pool, 1),
                "n_waves": wv_t,
                "pool": pool,
                "regen": True,
            }
            STATS.distribution(
                "Integrator/Wave occupancy", stats["mean_wave_occupancy"]
            )
        if obs_counters.enabled() and ctr_total:
            # the telemetry block: cumulative counters (checkpoint-
            # seeded, so resumed renders report end-to-end totals) and
            # the per-device wave and ray spread (ROADMAP multi-chip
            # metric; degenerate single entry off-mesh). Gated on the
            # kill switch, NOT just on the snapshot: a telemetry-off
            # resume of a telemetry-on checkpoint has a non-empty saved
            # snapshot that covers none of THIS process's work — report
            # nothing rather than stale partials as end-to-end totals
            # (the checkpoint keeps carrying the snapshot forward so a
            # later telemetry-on resume still reports true totals)
            from tpu_pbrt.accel.mxu import brute_tris

            stats["telemetry"] = {
                "counters": obs_counters.with_env_reads(obs_counters.with_brute_pairs(
                    ctr_total, brute_tris(scene.dev)
                ), scene.dev),
                **obs_counters.spread_telemetry(
                    jax.device_get(spread_counts), stats.get("n_waves"), rays
                ),
            }
            if "tstream" in scene.dev:
                # which branches the stream tracer took, for the widest
                # wave of this plan (the pool's fused 2R wave)
                from tpu_pbrt.accel.stream import branch_facts

                stats["telemetry"] |= branch_facts(
                    scene.dev["tstream"],
                    2 * pool if use_regen else plan.per_dev,
                )
        if metrics_on and phase_s:
            # per-phase wall totals for THIS render (the cross-render
            # histogram with percentiles lives in the METRICS registry;
            # bench.py summarizes it via obs.metrics.phase_summary).
            # Present only with the registry on, so TPU_PBRT_METRICS=0
            # pins the exact pre-registry stats dict.
            stats["phase_seconds"] = {
                k: round(v, 6) for k, v in sorted(phase_s.items())
            }
        TRACE.maybe_export()
        return RenderResult(
            image=img,
            film_state=state,
            seconds=secs,
            rays_traced=rays,
            mray_per_sec=rays / max(secs, 1e-9) / 1e6,
            spp=spp,
            completed_fraction=completed_fraction,
            stats=stats,
        )
