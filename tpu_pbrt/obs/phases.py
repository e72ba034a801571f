"""The ONE table of device-program phase names (ISSUE 25).

Every `jax.named_scope` in the device program takes its name from here,
and `obs/devtrace.py` attributes device time to the same tuple, so the
code and the reduction cannot drift. A scope is trace-time metadata: it
lands in the HLO op's `op_name` (the profiler's `tf_op` stat) and
changes nothing else of the lowered computation (tests/test_phases.py
proves it op for op).

Names nest by `/`; the part before the first `/` is the FAMILY the
reduction rolls up by. A name is pushed as ONE scope string
(`jax.named_scope("pool/regen")`), so in an op's path it is a
contiguous run of components and the reduction finds the DEEPEST one.

Imports nothing: the reduction reads traces without touching jax.
"""

from __future__ import annotations

# -- the per-dispatch step ---------------------------------------------------
CHUNK = "chunk"  # what chunk_fn / per_device_fn build (prepare_chunks)

# -- pool wavefront (PathIntegrator.pool_chunk body) -------------------------
POOL_REGEN = "pool/regen"  # free-slot rank + lane refill from the work counter
POOL_BOUNCE = "pool/bounce"  # one _bounce_wave (what no deeper scope claims)
POOL_DEPOSIT = "pool/deposit"  # the deposit sort + window gather + film add
#: the drain's while_loop itself: loop control and the carry's copies,
#: which XLA makes and `devtrace` places here by nesting (and, on the TPU,
#: the reduce-windows XLA rewrites the free-slot rank's cumsum into: it
#: names them after the while, not after `pool/regen`)
POOL_LOOP = "pool/loop"

# -- the calls into the acceleration structure -------------------------------
TRACE_CLOSEST = "trace/closest"  # scene_intersect
TRACE_SHADOW = "trace/shadow"  # scene_intersect_p / unoccluded_tr
#: the pool's 2R wave: continuation rays and the previous bounce's shadow
#: rays in ONE traversal — neither `closest` nor `shadow` alone
TRACE_FUSED = "trace/fused"

# -- stream tracer (accel/stream.py) -----------------------------------------
STREAM_SEED = "stream/seed"  # ray tables + the root-pair seed sort
STREAM_EXPAND = "stream/expand"  # _expand + _expand_push
STREAM_FLUSH = "stream/flush"  # _flush (the leaf matmul; merge apart)
STREAM_MERGE = "stream/merge"  # _merge_chunk
STREAM_FINALIZE = "stream/finalize"  # _finalize_hits
STREAM_LOOP = "stream/loop"  # the traversal's while_loop and its cond

# -- shading -----------------------------------------------------------------
SHADE_INTERACTION = "shade/interaction"  # make_interaction
SHADE_EMIT = "shade/emit"  # emitted radiance with forward MIS
SHADE_BSDF = "shade/bsdf"  # material evaluation + BSDF sampling
SHADE_NEE = "shade/nee"  # light sampling half (shadow trace apart)

# -- light sampling (core/lights_dev.py), opened where `shade/nee` and
# `shade/emit` stand (and wherever another integrator samples a light)
LIGHT_PICK = "light/pick"  # the voxel, the search of its CDF row, the pmf
LIGHT_SAMPLE = "light/sample"  # the row fetch, the point on the light, its pdf
LIGHT_PDF = "light/pdf"  # the MIS pdf of a hit on an emitter

# -- film (core/film.py) -----------------------------------------------------
FILM_DEPOSIT = "film/deposit"  # add_samples* / add_splats
FILM_MERGE = "film/merge"  # merge_film: accumulator + psum'd contribution
#: Film.develop is host numpy after a device_get today, so no device op
#: stands under it; the scope is where a device-side develop would land
FILM_DEVELOP = "film/develop"

# -- mesh (parallel/mesh.py) -------------------------------------------------
MESH_PSUM_FILM = "mesh/psum_film"  # the film contribution's all-reduce
MESH_PSUM_AUX = "mesh/psum_aux"  # the counters' all-reduce

# -- brute MXU intersection (accel/mxu.py) -----------------------------------
BRUTE_INTERSECT = "brute/intersect"

# -- samplers (core/sampling.py) ---------------------------------------------
#: the halton sampler's draws (sample_1d / sample_2d: the shuffled index,
#: the scrambled radical inverses, the pair select). Opened INSIDE the
#: halton branch only, so a program of another sampler never names it; the
#: other samplers' draws stand under the phase that asks for them
SAMPLER_HALTON = "sampler/halton"

#: every scope the program may open, in table order
PHASES = (
    CHUNK,
    POOL_LOOP, POOL_REGEN, POOL_BOUNCE, POOL_DEPOSIT,
    TRACE_CLOSEST, TRACE_SHADOW, TRACE_FUSED,
    STREAM_LOOP, STREAM_SEED, STREAM_EXPAND, STREAM_FLUSH, STREAM_MERGE,
    STREAM_FINALIZE,
    SHADE_INTERACTION, SHADE_EMIT, SHADE_BSDF, SHADE_NEE,
    LIGHT_PICK, LIGHT_SAMPLE, LIGHT_PDF,
    FILM_DEPOSIT, FILM_MERGE, FILM_DEVELOP,
    MESH_PSUM_FILM, MESH_PSUM_AUX,
    BRUTE_INTERSECT,
    SAMPLER_HALTON,
)

#: what reads `unscoped`: device time under no name of the table
UNSCOPED = "unscoped"


def family(phase: str) -> str:
    """`stream/expand` -> `stream`; `chunk` -> `chunk`."""
    return phase.split("/", 1)[0]


_BY_COMPONENTS = {tuple(p.split("/")): p for p in PHASES}
_MAX_LEN = max(len(k) for k in _BY_COMPONENTS)


def deepest(op_path: str) -> str:
    """The deepest vocabulary scope in an op's scope path
    (`jit(chunk_fn)/chunk/while/body/pool/regen/jit(cumsum)/cumsum:` ->
    `pool/regen`), or UNSCOPED. Whole components only: a function
    called `chunk_body` is not the scope `chunk`."""
    parts = op_path.rstrip(":").split("/")
    for end in range(len(parts), 0, -1):
        for n in range(min(_MAX_LEN, end), 0, -1):
            hit = _BY_COMPONENTS.get(tuple(parts[end - n:end]))
            if hit is not None:
                return hit
    return UNSCOPED
