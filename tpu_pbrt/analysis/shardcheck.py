"""shardcheck — static replicated-vs-varying analysis over shard_map bodies.

The mesh renderers run with jax's own varying-manual-axes check
(`check_vma`) on, so jax rejects at trace time an output that a
shard_map CLAIMS is replicated (out_spec `P()`) but that was never
reduced over the mesh axis. This pass is the second, independent
checker of the same invariant: it works on the jaxpr, needs no trace
of the real program to fail first, names the entry point and the axis,
and also sees what jax's type check does not (SC-LOOP-COLLECTIVE).
Deleting the film `psum` from `sharded_pool_renderer` must fail both.

For every `shard_map` equation found in an entry-point jaxpr, and every
mesh axis, an abstract interpreter walks the body tracking one bit per
value — *replicated* (every device holds the same value) or *varying*:

- inputs sharded over the axis (`in_specs` mentioning it) are varying;
  inputs with `P()` and closed-over constants are replicated;
- `axis_index` over the axis, `ppermute`, `all_to_all` and
  `psum_scatter` produce varying values;
- `psum`/`pmax`/`pmin` and (tiled) `all_gather` over the axis produce
  replicated values (whole-axis reductions only — `axis_index_groups`
  stays varying);
- every other primitive is replicated iff all its operands are;
- control flow recurses: `cond`/`switch` outputs are replicated only if
  every branch agrees AND the predicate is replicated; `while`/`scan`
  carries run to a fixpoint, and a while whose PREDICATE varies over the
  axis (per-device trip counts — the pool drain's designed freedom)
  makes every carry varying.

Rules:

SC-UNREDUCED        an output whose out_spec claims replication but
                    whose computed state is varying — the missing-psum
                    bug class. Error.
SC-LOOP-COLLECTIVE  a collective over the mesh axis inside a while_loop
                    whose trip count is device-varying — mismatched
                    collective counts deadlock the mesh (the reason
                    sharded_pool_renderer's contract bans collectives
                    inside the drain). Error.

Entry points: the pool and chunk mesh renderers (parallel/mesh.py) and
SPPM's three-phase mesh iteration (integrators/sppm.py — the all_gather
photon exchange). MLT's chain shard uses the same psum-at-the-end shape
as the chunk renderer and is exercised by tests/test_mlt.py's mesh leg.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from tpu_pbrt.analysis.cost import _is_literal

#: collectives that REPLICATE their output over the named axis
#: (`*_invariant` are the names jax gives psum/all_gather under check_vma)
_REDUCING = {"psum", "psum_invariant", "pmax", "pmin"}
_GATHERING = {"all_gather", "all_gather_invariant"}
#: collectives/queries that produce device-VARYING values over the axis
_VARYING_INTRO = {"ppermute", "pshuffle", "all_to_all", "psum_scatter",
                  "reduce_scatter"}

_CALL_LIKE = {"jit", "pjit", "closed_call", "core_call", "xla_call", "remat",
              "checkpoint", "custom_jvp_call", "custom_vjp_call",
              "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"}


@dataclass(frozen=True)
class ShardFinding:
    rule: str
    entry: str
    axis: str
    message: str
    severity: str = "error"

    def __str__(self) -> str:
        return (
            f"{self.entry}: {self.rule} [{self.severity}] "
            f"axis '{self.axis}': {self.message}"
        )


def _eqn_axes(eqn) -> Tuple[str, ...]:
    """Mesh axis names a collective equation operates over."""
    p = eqn.params
    axes = p.get("axes", p.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _whole_axis(eqn) -> bool:
    """Full-axis collective (axis_index_groups would split the axis into
    subgroups, which does NOT replicate over the whole axis)."""
    return eqn.params.get("axis_index_groups") is None


class _Env:
    """var -> replicated? with literal/constvar defaults."""

    def __init__(self) -> None:
        self._m: Dict[int, bool] = {}

    def read(self, v) -> bool:
        if _is_literal(v):
            return True
        return self._m.get(id(v), True)  # constvars/unknowns: replicated

    def write(self, v, rep: bool) -> None:
        self._m[id(v)] = rep


def _has_axis_collective(jaxpr, axis: str) -> bool:
    """Any collective over `axis` anywhere under this jaxpr? Reuses the
    audit layer's sub-jaxpr traversal so a jax version that renames a
    call primitive's jaxpr param needs fixing in exactly one place."""
    from tpu_pbrt.analysis.audit import iter_jaxprs

    return any(
        eqn.primitive.name in (_REDUCING | _GATHERING | _VARYING_INTRO)
        and axis in _eqn_axes(eqn)
        for j in iter_jaxprs(jaxpr)
        for eqn in j.eqns
    )


def _run_body(
    jaxpr, axis: str, in_rep: Sequence[bool], entry: str,
    findings: List[ShardFinding],
) -> List[bool]:
    """Forward replication analysis of one (open) jaxpr. in_rep aligns
    with jaxpr.invars; returns the states of jaxpr.outvars."""
    env = _Env()
    for v, r in zip(jaxpr.invars, in_rep):
        env.write(v, bool(r))
    for v in jaxpr.constvars:
        env.write(v, True)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ins = [env.read(v) for v in eqn.invars]

        if name in _REDUCING or name in _GATHERING:
            rep = axis in _eqn_axes(eqn) and _whole_axis(eqn)
            out = rep or all(ins)
            for v in eqn.outvars:
                env.write(v, out)
            continue
        if name == "axis_index":
            varying = axis in _eqn_axes(eqn)
            for v in eqn.outvars:
                env.write(v, not varying)
            continue
        if name in _VARYING_INTRO:
            touched = axis in _eqn_axes(eqn)
            for v in eqn.outvars:
                env.write(v, all(ins) and not touched)
            continue

        if name == "while":
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            cond_j = eqn.params["cond_jaxpr"].jaxpr
            body_j = eqn.params["body_jaxpr"].jaxpr
            cconsts = ins[:cn]
            bconsts = ins[cn:cn + bn]
            carry = list(ins[cn + bn:])
            for _ in range(len(carry) + 2):
                pred = _run_body(
                    cond_j, axis, cconsts + carry, entry, findings
                )[0]
                new = _run_body(body_j, axis, bconsts + carry, entry, findings)
                if not pred:
                    new = [False] * len(new)
                joined = [a and b for a, b in zip(carry, new)]
                if joined == carry:
                    break
                carry = joined
            pred = _run_body(cond_j, axis, cconsts + carry, entry, findings)[0]
            if not pred and _has_axis_collective(body_j, axis):
                f = ShardFinding(
                    "SC-LOOP-COLLECTIVE", entry, axis,
                    "collective over the mesh axis inside a while_loop "
                    "whose trip count is device-varying — devices would "
                    "issue mismatched collective counts (deadlock); "
                    "hoist the reduction out of the drain loop",
                )
                if f not in findings:
                    findings.append(f)
            for v, r in zip(eqn.outvars, carry):
                env.write(v, r)
            continue

        if name == "scan":
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            body_j = eqn.params["jaxpr"].jaxpr
            consts = ins[:nc]
            carry = list(ins[nc:nc + ncar])
            xs = ins[nc + ncar:]  # per-iteration slices keep their state
            ys: List[bool] = []
            for _ in range(len(carry) + 2):
                out = _run_body(
                    body_j, axis, consts + carry + xs, entry, findings
                )
                new_carry = out[:ncar]
                ys = out[ncar:]
                joined = [a and b for a, b in zip(carry, new_carry)]
                if joined == carry:
                    break
                carry = joined
            for v, r in zip(eqn.outvars, carry + ys):
                env.write(v, r)
            continue

        if name == "cond":
            pred = ins[0]
            ops = ins[1:]
            outs: Optional[List[bool]] = None
            for br in eqn.params["branches"]:
                o = _run_body(br.jaxpr, axis, ops, entry, findings)
                outs = o if outs is None else [a and b for a, b in zip(outs, o)]
            outs = outs or []
            if not pred:
                outs = [False] * len(outs)
            for v, r in zip(eqn.outvars, outs):
                env.write(v, r)
            continue

        if name in _CALL_LIKE:
            sub = None
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                if key in eqn.params:
                    sub = eqn.params[key]
                    break
            if sub is not None:
                from jax.extend import core

                inner = sub.jaxpr if isinstance(sub, core.ClosedJaxpr) else sub
                outs = _run_body(inner, axis, ins, entry, findings)
                for v, r in zip(eqn.outvars, outs):
                    env.write(v, r)
                continue

        if name == "shard_map":
            # nested shard_map: checked on its own when discovered by
            # scan_closed_jaxpr; treat its outputs per its out_specs
            for v, spec in zip(eqn.outvars, eqn.params["out_specs"]):
                claimed = axis not in _spec_axes(spec)
                env.write(v, claimed and all(ins))
            continue

        # default transfer: replicated iff every operand is
        out = all(ins)
        for v in eqn.outvars:
            env.write(v, out)

    return [env.read(v) for v in jaxpr.outvars]


def _spec_axes(spec) -> Tuple[str, ...]:
    """Mesh axis names a PartitionSpec shards over (its entries are
    None, one axis name, or a tuple of them)."""
    out: List[str] = []
    for v in spec:
        if isinstance(v, str):
            out.append(v)
        elif v is not None:
            out.extend(v)
    return tuple(out)


def check_shard_map_eqn(eqn, entry: str) -> List[ShardFinding]:
    """Verify one shard_map equation: every output whose out_spec claims
    replication over a mesh axis must be computed replicated."""
    findings: List[ShardFinding] = []
    mesh = eqn.params["mesh"]
    in_specs = eqn.params["in_specs"]
    out_specs = eqn.params["out_specs"]
    body = eqn.params["jaxpr"]
    for axis in mesh.axis_names:
        if not isinstance(axis, str):
            continue
        in_rep = [axis not in _spec_axes(n) for n in in_specs]
        out_rep = _run_body(body, axis, in_rep, entry, findings)
        for i, (spec, rep) in enumerate(zip(out_specs, out_rep)):
            claimed = axis not in _spec_axes(spec)
            if claimed and not rep:
                findings.append(
                    ShardFinding(
                        "SC-UNREDUCED", entry, axis,
                        f"shard_map output #{i} is claimed replicated "
                        f"(out_spec P()) but is device-varying — missing "
                        f"psum/all_gather over '{axis}' before return",
                    )
                )
    return findings


def scan_closed_jaxpr(closed_jaxpr, entry: str) -> Tuple[List[ShardFinding], int]:
    """Find every shard_map equation under `closed_jaxpr` (including
    inside pjit bodies) and check each. Returns (findings, n_checked)."""
    from tpu_pbrt.analysis.audit import iter_jaxprs

    findings: List[ShardFinding] = []
    n = 0
    for j in iter_jaxprs(closed_jaxpr.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name == "shard_map":
                n += 1
                findings.extend(check_shard_map_eqn(eqn, entry))
    return findings, n


# --------------------------------------------------------------------------
# entry points (share audit.py's cached tiny scenes)
# --------------------------------------------------------------------------


def chunk_step_jaxpr():
    """Trace a sharded_chunk_renderer step over the stream scene — the
    fixed-batch mesh path (film psum at the end of every chunk)."""
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.analysis.audit import _stream_scene
    from tpu_pbrt.core.film import merge_film
    from tpu_pbrt.parallel.mesh import make_mesh, sharded_chunk_renderer

    scene, integ = _stream_scene("path")
    film = scene.film
    n_dev = len(jax.devices())
    mesh = make_mesh(n_dev)
    n = 64

    def per_device_fn(dev, start):
        # start: this device's (1, 2) shard — feeds the wave so the
        # film contribution is genuinely device-varying pre-psum
        px = (start[0, 0] + jnp.arange(n, dtype=jnp.int32)) % 16
        py = jnp.zeros((n,), jnp.int32)
        o = jnp.zeros((n, 3), jnp.float32)
        d = jnp.tile(jnp.asarray([0.0, 0.0, 1.0], jnp.float32), (n, 1))
        s = jnp.zeros((n,), jnp.int32)
        L, nrays = integ.li(dev, o, d, px, py, s)
        contrib = film.add_samples_pixel(
            film.init_state(), px, py, L, jnp.ones((n,), bool),
            jnp.ones((n,), jnp.float32),
        )
        return contrib, jnp.sum(nrays)

    step = sharded_chunk_renderer(mesh, per_device_fn)

    def fn(fs, starts):
        contrib, nrays = step(scene.dev, starts)
        return merge_film(fs, contrib), nrays

    starts = jnp.zeros((n_dev, 2), jnp.int32)
    return jax.make_jaxpr(fn)(film.init_state(), starts)


def sppm_mesh_jaxpr():
    """Trace one full SPPM mesh iteration (cam/photon/gather shard_maps
    with the ICI all_gather photon exchange)."""
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.analysis.audit import _cornell_scene
    from tpu_pbrt.integrators.sppm import _SPPMState
    from tpu_pbrt.parallel.mesh import make_mesh

    scene, integ = _cornell_scene("sppm")
    film = scene.film
    x0, x1, y0, y1 = film.sample_bounds()
    w, h = x1 - x0, y1 - y0
    P = w * h
    pix = jnp.arange(P, dtype=jnp.int32)
    px = x0 + pix % w
    py = y0 + pix // w
    state = _SPPMState(
        r2=jnp.full((P,), 1.0, jnp.float32),
        n=jnp.zeros((P,), jnp.float32),
        tau=jnp.zeros((P, 3), jnp.float32),
        ld=jnp.zeros((P, 3), jnp.float32),
        dropped=jnp.zeros((), jnp.int32),
    )
    mesh = make_mesh(len(jax.devices()))
    iteration, state, _ = integ._mesh_iteration(
        scene.dev, mesh, state, px, py, P, 64
    )
    return jax.make_jaxpr(lambda st: iteration(st, jnp.int32(0)))(state)


def default_entry_points():
    from tpu_pbrt.analysis import audit

    return {
        "sharded_pool_renderer": audit.mesh_step_jaxpr,
        "sharded_chunk_renderer": chunk_step_jaxpr,
        "sppm.mesh_iteration": sppm_mesh_jaxpr,
    }


def run_shardcheck(entries=None) -> Tuple[List[str], List[str]]:
    """CLI/test driver. Returns (errors, warnings): SC findings and trace
    crashes are errors; an entry point with no shard_map inside would
    mean the mesh path silently stopped being a shard_map program — also
    an error (the check would be vacuous)."""
    entries = entries if entries is not None else default_entry_points()
    errors: List[str] = []
    warnings: List[str] = []
    for name, fn in entries.items():
        try:
            # trace AND check under the same guard: a jax release that
            # renames a shard_map param must degrade to a reported entry
            # error, not a CLI traceback (crashes reported, never raised)
            jx = fn()
            findings, n = scan_closed_jaxpr(jx, name)
        except Exception as e:  # noqa: BLE001
            errors.append(
                f"{name}: shardcheck crashed: {type(e).__name__}: {e}"
            )
            continue
        if n == 0:
            errors.append(
                f"{name}: no shard_map equation found — the mesh entry "
                "point no longer lowers through shard_map; shardcheck "
                "has nothing to verify"
            )
        errors.extend(
            str(f) for f in findings if f.severity == "error"
        )
        warnings.extend(
            str(f) for f in findings if f.severity != "error"
        )
    return errors, warnings
