"""jaxcost — static roofline budgets over the real entry-point jaxprs.

Whenever no accelerator can be reached, perf regressions are invisible
to a measured metric. This pass closes that gap with a signal that
needs NO hardware: an abstract interpreter walks the closed jaxpr of
every hot entry point (path wave, pool drain, stream traversal, film
deposits, sharded mesh step) and charges each equation a FLOP count and
an HBM bytes-moved count from a per-primitive model. The rollup is a
static roofline per entry point — flops, bytes, arithmetic intensity —
committed to `tpu_pbrt/analysis/budgets.json` and re-checked by
`python -m tpu_pbrt.analysis`: an entry point whose bytes or FLOPs grow
beyond tolerance fails CI even when `jax.devices()` would hang.

The byte model is deliberately the UNFUSED upper bound: every equation
reads its (non-literal) inputs and writes its outputs at HBM. XLA fusion
makes the true traffic lower, but the proxy is deterministic, stable
across runs, and moves in the same direction as the real number — which
is all a regression gate needs. Loop bodies are charged ONCE (a
`while_loop` body is exactly one wave of the drain loop, so the pool
rollup reads as "per wave"); `scan` bodies multiply by their static trip
count.

On top of the rollup, the walk reports anti-pattern findings:

JC-CHURN     dtype round trip (A -> B -> A `convert_element_type` chain
             through elementwise ops) at or above wave width — each
             round trip is two full-array HBM passes that a dtype-stable
             formulation deletes.
JC-RELAYOUT  `transpose` of a buffer >= RELAYOUT_MIN_BYTES inside the
             wave — a relayout copy paid per dispatch that can usually
             be hoisted to scene-compile time.
JC-GATHER    a gather whose slice rows are narrower than
             GATHER_MIN_SLICE_BYTES while the index count exceeds
             GATHER_INDEX_FACTOR x the wave width and the fetched total
             exceeds GATHER_MIN_TOTAL_BYTES — random access far off the
             measured ~bandwidth regime of batched row copies. Gathers
             whose indices provably derive from a `sort` output are
             exempt: nearly-sorted random access measures ~1 ns/element
             on this v5e (accel/stream.py module doc), and sorting
             before gathering is exactly the sanctioned fix.
JC-BCAST     `broadcast_in_dim` materializing >= BCAST_MIN_RATIO x a
             NON-SCALAR input at >= BCAST_MIN_BYTES output — a blowup
             XLA may have to materialize (scalar broadcasts fuse for
             free and are never flagged).
JC-PAD       an output >= PAD_MIN_BYTES whose trailing dims waste more
             than PAD_MIN_WASTE of the (8, 128) f32 vector-memory tile
             (scaled by dtype width) — HBM and VMEM pay the padded shape.

Deliberate violations (the one-hot MXU gather replacement packs i32 ids
through f32 matmul lanes by design) are waived in `WAIVERS` with a
reason, so the finding list stays actionable.
"""

from __future__ import annotations

import hashlib
import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

# --------------------------------------------------------------------------
# per-primitive cost model
# --------------------------------------------------------------------------

#: flops-per-element weight for transcendental / iterative elementwise ops
_TRANSCENDENTAL = {
    "exp", "exp2", "expm1", "log", "log1p", "log2", "sin", "cos", "tan",
    "asin", "acos", "atan", "atan2", "sinh", "cosh", "tanh", "asinh",
    "acosh", "atanh", "pow", "rsqrt", "sqrt", "cbrt", "erf", "erfc",
    "erf_inv", "logistic", "lgamma", "digamma", "regularized_incomplete_beta",
}
_TRANSCENDENTAL_WEIGHT = 8

#: pure data-movement primitives: 0 flops, bytes only
_MOVEMENT = {
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "expand_dims",
    "concatenate", "pad", "slice", "rev", "copy", "convert_element_type",
    "bitcast_convert_type", "iota", "real", "imag", "device_put",
}

#: reductions: flops = input elements
_REDUCTIONS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "reduce_precision",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
}

_SCATTERS = {"scatter", "scatter-add", "scatter_add", "scatter_mul",
             "scatter_min", "scatter_max", "scatter-update"}

#: sub-jaxpr carrying primitives handled structurally in _walk
_CONTROL = {"while", "scan", "cond", "jit", "pjit", "closed_call", "remat",
            "checkpoint", "custom_jvp_call", "custom_vjp_call",
            "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr", "shard_map",
            "core_call", "xla_call"}


def _aval_elems(aval) -> int:
    shape = getattr(aval, "shape", ())
    n = 1
    for s in shape:
        n *= int(s)
    return n


def _aval_bytes(aval) -> int:
    dt = getattr(aval, "dtype", None)
    if dt is None:  # token / abstract unit values
        return 0
    return _aval_elems(aval) * dt.itemsize


def _is_literal(v) -> bool:
    return not hasattr(v, "count")  # core.Var has .count; Literal does not


def _eqn_bytes(eqn) -> int:
    """HBM traffic proxy: read every non-literal input, write every
    output. Gather reads only the fetched slices (not the whole source
    table — a 2-line wave must not be charged the full scene); scatter
    and dynamic_update_slice read AND write their full operand (XLA
    materializes the copy unless it can alias)."""
    name = eqn.primitive.name
    outs = sum(_aval_bytes(v.aval) for v in eqn.outvars)
    if name == "gather":
        idx = _aval_bytes(eqn.invars[1].aval) if len(eqn.invars) > 1 else 0
        return 2 * outs + idx  # slices read + output written + indices
    if name in _SCATTERS:
        operand = _aval_bytes(eqn.invars[0].aval)
        rest = sum(
            _aval_bytes(v.aval)
            for v in eqn.invars[1:]
            if not _is_literal(v)
        )
        return 2 * operand + rest
    if name == "dynamic_update_slice":
        operand = _aval_bytes(eqn.invars[0].aval)
        update = _aval_bytes(eqn.invars[1].aval)
        return 2 * operand + update
    ins = sum(
        _aval_bytes(v.aval) for v in eqn.invars if not _is_literal(v)
    )
    return ins + outs


def _eqn_flops(eqn) -> int:
    name = eqn.primitive.name
    out_elems = sum(_aval_elems(v.aval) for v in eqn.outvars)
    if name in _MOVEMENT:
        return 0
    if name == "dot_general":
        (lhs_c, _), _ = eqn.params["dimension_numbers"]
        lhs_shape = eqn.invars[0].aval.shape
        k = 1
        for i in lhs_c:
            k *= int(lhs_shape[i])
        return 2 * k * out_elems
    if name in _REDUCTIONS or name.startswith("reduce_"):
        return sum(
            _aval_elems(v.aval) for v in eqn.invars if not _is_literal(v)
        )
    if name == "sort":
        n = max(_aval_elems(eqn.invars[0].aval), 2)
        return int(n * math.log2(n)) * len(eqn.invars)
    if name == "gather":
        return out_elems
    if name in _SCATTERS:
        return sum(
            _aval_elems(v.aval)
            for v in eqn.invars[2:]
            if not _is_literal(v)
        ) or out_elems
    if name in ("threefry2x32", "random_bits"):
        return 16 * out_elems  # ~13 rounds of ARX per counter pair
    if name in _TRANSCENDENTAL:
        return _TRANSCENDENTAL_WEIGHT * out_elems
    if name == "integer_pow":
        return 2 * out_elems
    if name == "select_n":
        return out_elems
    return out_elems  # default: one op per output element


# --------------------------------------------------------------------------
# rollup + findings containers
# --------------------------------------------------------------------------


@dataclass
class Rollup:
    """Static roofline for one entry point. Loop bodies count once, so
    for the drain/traversal loops this reads as cost per wave."""

    entry: str
    flops: int = 0
    hbm_bytes: int = 0
    eqns: int = 0
    n_dynamic_loops: int = 0
    fingerprint: str = ""

    @property
    def intensity(self) -> float:
        return self.flops / max(self.hbm_bytes, 1)

    def to_json(self) -> Dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "eqns": self.eqns,
            "fingerprint": self.fingerprint,
        }


@dataclass(frozen=True)
class Finding:
    rule: str
    entry: str
    detail: str
    severity: str = "warning"
    waived: Optional[str] = None  # reason, when waived

    @property
    def finding_id(self) -> str:
        return f"{self.rule}:{self.entry}:{self.detail.split(' @ ')[0]}"

    def __str__(self) -> str:
        w = f" (waived: {self.waived})" if self.waived else ""
        return f"{self.entry}: {self.rule} [{self.severity}] {self.detail}{w}"


# thresholds (module constants so the adversarial tests can reference them)
CHURN_MIN_ELEMS = 64
RELAYOUT_MIN_BYTES = 1 << 16
GATHER_MIN_SLICE_BYTES = 16
GATHER_MIN_TOTAL_BYTES = 1 << 16
GATHER_INDEX_FACTOR = 4
BCAST_MIN_BYTES = 1 << 20
BCAST_MIN_RATIO = 64
PAD_MIN_BYTES = 1 << 20
PAD_MIN_WASTE = 1.0

#: (rule, entry substring, detail substring) -> reason. Deliberate
#: violations stay visible in --format json (waived, severity "info")
#: but do not fail the gate and are excluded from the text summary.
WAIVERS: List[Tuple[str, str, str, str]] = [
    (
        "JC-RELAYOUT", "", "perm=(1, 0, 2)",
        "flush feature build: the (CH, 8, BLOCK) swap feeds phi rows to "
        "the leaf matmul lane-major by design — the profiled layout of "
        "accel/stream.py _flush; hoisting is impossible (per-wave data)",
    ),
]


def _waiver_for(rule: str, entry: str, detail: str) -> Optional[str]:
    for r, e, d, reason in WAIVERS:
        if r == rule and e in entry and d in detail:
            return reason
    return None


# --------------------------------------------------------------------------
# the abstract interpreter
# --------------------------------------------------------------------------


class _Walk:
    def __init__(self, entry: str, wave_width: int):
        self.entry = entry
        self.wave = max(int(wave_width), 1)
        self.flops = 0
        self.bytes = 0
        self.eqns = 0
        self.n_dynamic_loops = 0
        self.findings: List[Finding] = []
        self._fp = hashlib.sha256()
        #: var id -> source dtype string of the convert chain it carries
        self._churn_src: Dict[int, Tuple[str, int]] = {}
        #: var ids that provably derive from a lax.sort output — gathers
        #: at such indices are the sanctioned near-bandwidth pattern
        self._sorted: set = set()

    # -- findings ------------------------------------------------------
    def _emit(self, rule: str, detail: str) -> None:
        waived = _waiver_for(rule, self.entry, detail)
        f = Finding(
            rule, self.entry, detail,
            severity="info" if waived else "warning", waived=waived,
        )
        if f not in self.findings:
            self.findings.append(f)

    def _check_churn(self, eqn) -> None:
        """A -> B -> A convert chain: tag each convert's output with the
        dtype it LEFT, propagate the tag through shape ops and cheap
        elementwise ops whose other operands are literals, and flag when
        a later convert lands back on the tagged source dtype."""
        name = eqn.primitive.name
        if name == "convert_element_type":
            src_v = eqn.invars[0]
            out_v = eqn.outvars[0]
            src_dt = str(src_v.aval.dtype)
            out_dt = str(out_v.aval.dtype)
            if src_dt == out_dt:
                return
            tag = self._churn_src.get(id(src_v))
            elems = _aval_elems(out_v.aval)
            if tag is not None and tag[0] == out_dt and elems >= CHURN_MIN_ELEMS:
                self._emit(
                    "JC-CHURN",
                    f"{out_dt}->{src_dt}->{out_dt} round trip "
                    f"@ {elems} elems — two convert passes over the "
                    "array; keep one dtype through the chain",
                )
            else:
                self._churn_src[id(out_v)] = (src_dt, elems)
            return
        # propagation: shape-preserving movement and cheap arithmetic
        # whose other operands are literals keep the tag alive
        prop = name in (
            "reshape", "transpose", "squeeze", "expand_dims",
            "broadcast_in_dim", "slice", "copy",
        ) or (
            name in ("add", "sub", "mul", "max", "min", "neg", "clamp")
            and sum(0 if _is_literal(v) else 1 for v in eqn.invars) == 1
        )
        if prop:
            for v in eqn.invars:
                if not _is_literal(v) and id(v) in self._churn_src:
                    for ov in eqn.outvars:
                        self._churn_src[id(ov)] = self._churn_src[id(v)]
                    break

    def _track_sorted(self, eqn) -> None:
        name = eqn.primitive.name
        if name == "sort":
            for ov in eqn.outvars:
                self._sorted.add(id(ov))
            return
        # order-preserving-enough propagation: clip/offset/reshape keep
        # a sorted index stream nearly sorted; select_n (jnp.where used
        # to mask lanes) keeps the surviving runs sorted
        prop = name in (
            "reshape", "slice", "squeeze", "expand_dims",
            "broadcast_in_dim", "copy", "convert_element_type",
            "max", "min", "clamp", "select_n",
        ) or (
            name in ("add", "sub")
            and sum(0 if _is_literal(v) else 1 for v in eqn.invars) == 1
        )
        if prop and any(
            not _is_literal(v) and id(v) in self._sorted
            for v in eqn.invars
        ):
            for ov in eqn.outvars:
                self._sorted.add(id(ov))

    def _check_patterns(self, eqn) -> None:
        name = eqn.primitive.name
        self._check_churn(eqn)
        self._track_sorted(eqn)
        if name == "transpose":
            nbytes = _aval_bytes(eqn.invars[0].aval)
            if nbytes >= RELAYOUT_MIN_BYTES:
                shape = tuple(eqn.invars[0].aval.shape)
                self._emit(
                    "JC-RELAYOUT",
                    f"transpose of {nbytes} B buffer {shape} "
                    f"@ perm={eqn.params.get('permutation')} — a relayout "
                    "copy per wave; hoist to build time or keep the "
                    "consumer layout",
                )
        elif name == "gather" and len(eqn.invars) > 1:
            idx_v = eqn.invars[1]
            out_b = _aval_bytes(eqn.outvars[0].aval)
            idx_shape = idx_v.aval.shape
            n_idx = _aval_elems(idx_v.aval) // max(
                idx_shape[-1] if idx_shape else 1, 1
            )
            slice_bytes = out_b // max(n_idx, 1)
            sorted_idx = _is_literal(idx_v) or id(idx_v) in self._sorted
            # only FLAT index streams ((N, d) indices) are candidate
            # random access; a multi-dim index block is a batched
            # take_along_axis whose picks stay local to their own row
            flat_idx = len(idx_shape) <= 2
            if (
                0 < slice_bytes < GATHER_MIN_SLICE_BYTES
                and out_b >= GATHER_MIN_TOTAL_BYTES
                and n_idx > GATHER_INDEX_FACTOR * self.wave
                and flat_idx
                and not sorted_idx
            ):
                self._emit(
                    "JC-GATHER",
                    f"narrow gather: {slice_bytes} B/row over {n_idx} "
                    f"indices (wave width {self.wave}) — random access "
                    "far past wave width; batch rows or sort indices",
                )
        elif name == "broadcast_in_dim":
            out_b = _aval_bytes(eqn.outvars[0].aval)
            in_elems = sum(
                _aval_elems(v.aval)
                for v in eqn.invars
                if not _is_literal(v)
            )
            in_b = max(
                sum(
                    _aval_bytes(v.aval)
                    for v in eqn.invars
                    if not _is_literal(v)
                ),
                1,
            )
            if (
                in_elems > 1  # scalar broadcasts fuse for free
                and out_b >= BCAST_MIN_BYTES
                and out_b // in_b >= BCAST_MIN_RATIO
            ):
                self._emit(
                    "JC-BCAST",
                    f"broadcast blowup {in_b} B -> {out_b} B "
                    f"({out_b // in_b}x) @ {tuple(eqn.outvars[0].aval.shape)}"
                    " — XLA may materialize the expansion",
                )
        for ov in eqn.outvars:
            self._check_pad(ov)

    def _check_pad(self, v) -> None:
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is None or len(aval.shape) < 2:
            return
        nbytes = _aval_bytes(aval)
        if nbytes < PAD_MIN_BYTES:
            return
        # TPU vector memory tiles f32 as (8, 128) over the two minor
        # dims; narrower dtypes pack proportionally more sublanes
        sub = max(8 * 4 // max(dt.itemsize, 1), 8)
        s0, s1 = int(aval.shape[-2]), int(aval.shape[-1])
        padded = -(-s0 // sub) * sub * (-(-s1 // 128) * 128)
        waste = padded / max(s0 * s1, 1) - 1.0
        if waste > PAD_MIN_WASTE:
            self._emit(
                "JC-PAD",
                f"padding waste {waste:.1f}x on {tuple(aval.shape)} "
                f"{dt} ({nbytes} B) @ (8,128)-tile — pad or re-layout "
                "the trailing dims",
            )

    # -- structural walk -----------------------------------------------
    def _charge(self, flops: int, nbytes: int, mult: int) -> None:
        self.flops += flops * mult
        self.bytes += nbytes * mult

    def walk(self, jaxpr, mult: int = 1) -> None:
        from jax.extend import core

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            self.eqns += 1
            self._fp.update(name.encode())
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None:
                    self._fp.update(
                        f"{getattr(aval, 'shape', ())}"
                        f"{getattr(aval, 'dtype', '')}".encode()
                    )
            if name == "while":
                # dynamic trip count: body charged ONCE (one wave)
                self.n_dynamic_loops += 1
                self.walk(eqn.params["cond_jaxpr"].jaxpr, mult)
                self.walk(eqn.params["body_jaxpr"].jaxpr, mult)
                continue
            if name == "scan":
                self.walk(
                    eqn.params["jaxpr"].jaxpr,
                    mult * max(int(eqn.params.get("length", 1)), 1),
                )
                continue
            if name == "cond":
                # one branch executes: charge the most expensive one
                best = None
                for br in eqn.params["branches"]:
                    sub = _Walk(self.entry, self.wave)
                    sub.walk(br.jaxpr, 1)
                    if best is None or sub.bytes > best.bytes:
                        best = sub
                    self._merge_findings(sub)
                    self.eqns += sub.eqns
                    self.n_dynamic_loops += sub.n_dynamic_loops
                    self._fp.update(sub._fp.digest())
                if best is not None:
                    self._charge(best.flops, best.bytes, mult)
                continue
            if name in _CONTROL:
                sub = None
                for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                    if key in eqn.params:
                        sub = eqn.params[key]
                        break
                if sub is not None:
                    inner = sub.jaxpr if isinstance(
                        sub, core.ClosedJaxpr
                    ) else sub
                    # call-like boundaries (jnp.clip and friends wrap in
                    # pjit) bind fresh inner vars positionally: carry the
                    # sorted/churn tags across, both directions, so a
                    # sort -> clip -> gather chain stays visible
                    for outer, iv in zip(eqn.invars, inner.invars):
                        if _is_literal(outer):
                            continue
                        if id(outer) in self._sorted:
                            self._sorted.add(id(iv))
                        if id(outer) in self._churn_src:
                            self._churn_src[id(iv)] = self._churn_src[
                                id(outer)
                            ]
                    self.walk(inner, mult)
                    for iv, outer in zip(inner.outvars, eqn.outvars):
                        if _is_literal(iv):
                            continue
                        if id(iv) in self._sorted:
                            self._sorted.add(id(outer))
                        if id(iv) in self._churn_src:
                            self._churn_src[id(outer)] = self._churn_src[
                                id(iv)
                            ]
                    continue
            self._charge(_eqn_flops(eqn), _eqn_bytes(eqn), mult)
            self._check_patterns(eqn)

    def _merge_findings(self, sub: "_Walk") -> None:
        for f in sub.findings:
            if f not in self.findings:
                self.findings.append(f)


def analyze_jaxpr(
    closed_jaxpr, entry: str, wave_width: int = 1
) -> Tuple[Rollup, List[Finding]]:
    """Roll up (flops, HBM bytes, fingerprint) and anti-pattern findings
    for one entry-point ClosedJaxpr."""
    w = _Walk(entry, wave_width)
    w.walk(closed_jaxpr.jaxpr)
    # constants enter the program once per dispatch
    w.bytes += sum(
        _aval_bytes(v.aval) for v in closed_jaxpr.jaxpr.constvars
    )
    roll = Rollup(
        entry=entry,
        flops=w.flops,
        hbm_bytes=w.bytes,
        eqns=w.eqns,
        n_dynamic_loops=w.n_dynamic_loops,
        fingerprint=w._fp.hexdigest()[:16],
    )
    return roll, w.findings


# --------------------------------------------------------------------------
# entry-point registry (shares audit.py's cached tiny scenes)
# --------------------------------------------------------------------------


def default_entry_points():
    """name -> () -> (ClosedJaxpr, wave_width). Import-deferred: building
    them traces real programs over audit.py's lru-cached scenes."""
    from tpu_pbrt.analysis import audit

    return {
        "path.li": lambda: (audit.integrator_li_jaxpr("path"), 64),
        "pool_chunk": lambda: (audit.pool_chunk_jaxpr(), 64),
        "stream_intersect": lambda: (audit.stream_traversal_jaxpr(), 128),
        "film.add_samples": lambda: (audit.film_deposit_jaxpr(), 64),
        "film.add_samples_pixel": lambda: (
            audit.film_deposit_jaxpr(pixel_path=True), 64,
        ),
        "mesh_step": lambda: (audit.mesh_step_jaxpr(), 64),
        # the render service's slice dispatch (ISSUE 6): same pool drain,
        # service-shaped slice width — the serving hot path's own budget
        "serve_step": lambda: (audit.serve_step_jaxpr(), 64),
    }


def collect_rollups(
    entries=None,
) -> Tuple[Dict[str, Rollup], List[Finding], List[str]]:
    """Trace every entry point. Returns (rollups, findings, crashes) —
    a crash is reported, never raised (the CLI must print a full report)."""
    entries = entries if entries is not None else default_entry_points()
    rollups: Dict[str, Rollup] = {}
    findings: List[Finding] = []
    crashes: List[str] = []
    for name, fn in entries.items():
        try:
            jx, wave = fn()
            roll, f = analyze_jaxpr(jx, name, wave)
            rollups[name] = roll
            findings.extend(f)
        except Exception as e:  # noqa: BLE001
            crashes.append(f"{name}: cost trace crashed: {type(e).__name__}: {e}")
    return rollups, findings, crashes


# --------------------------------------------------------------------------
# the budget gate
# --------------------------------------------------------------------------

BUDGETS_PATH = Path(__file__).resolve().parent / "budgets.json"
DEFAULT_TOLERANCE = 0.10


def load_budgets(path: Optional[Path] = None) -> Dict:
    p = Path(path) if path is not None else BUDGETS_PATH
    if not p.exists():
        return {"tolerance": DEFAULT_TOLERANCE, "entries": {}}
    return json.loads(p.read_text())


def save_budgets(
    rollups: Dict[str, Rollup], path: Optional[Path] = None,
    tolerance: float = DEFAULT_TOLERANCE,
) -> Path:
    import jax

    p = Path(path) if path is not None else BUDGETS_PATH
    data = {
        "_comment": (
            "Static per-entry-point roofline budgets (jaxcost, ISSUE 3). "
            "Regenerate with `python -m tpu_pbrt.analysis "
            "--update-budgets` after an INTENTIONAL hot-path change; "
            "CI fails when flops/bytes drift past tolerance."
        ),
        "tolerance": tolerance,
        # the counts depend on how THIS jax version lowers jnp ops to
        # primitives; record it so cross-version drift is diagnosable
        "jax_version": jax.__version__,
        "entries": {k: r.to_json() for k, r in sorted(rollups.items())},
    }
    p.write_text(json.dumps(data, indent=2, sort_keys=True) + "\n")
    return p


def check_budgets(
    rollups: Dict[str, Rollup], budgets: Dict
) -> Tuple[List[str], List[str]]:
    """Compare fresh rollups against committed budgets. Returns
    (errors, warnings): regressions beyond tolerance are errors;
    improvements beyond tolerance and fingerprint drift are warnings
    nudging a `--update-budgets` ratchet."""
    errors: List[str] = []
    warnings: List[str] = []
    tol = float(budgets.get("tolerance", DEFAULT_TOLERANCE))
    committed = budgets.get("entries", {})
    rec_ver = budgets.get("jax_version")
    if rec_ver:
        import jax

        if jax.__version__ != rec_ver:
            warnings.append(
                f"budgets.json was generated under jax {rec_ver}; this "
                f"process runs jax {jax.__version__} — primitive "
                "lowering differs across versions, so metric drift below "
                "may be the jax upgrade, not your change (refresh with "
                "--update-budgets on the CI jax version)"
            )
    for name, roll in sorted(rollups.items()):
        b = committed.get(name)
        if b is None:
            errors.append(
                f"{name}: no committed budget — run "
                "`python -m tpu_pbrt.analysis --update-budgets` and "
                "commit budgets.json"
            )
            continue
        for metric, fresh in (("flops", roll.flops),
                              ("hbm_bytes", roll.hbm_bytes)):
            base = int(b.get(metric, 0))
            if base <= 0:
                continue
            ratio = fresh / base
            if ratio > 1.0 + tol:
                errors.append(
                    f"{name}: static {metric} regressed {ratio:.2f}x "
                    f"({base} -> {fresh}, tolerance {tol:.0%}) — fix the "
                    "hot path or, if intentional, refresh with "
                    "--update-budgets"
                )
            elif ratio < 1.0 - tol:
                warnings.append(
                    f"{name}: static {metric} improved {ratio:.2f}x "
                    f"({base} -> {fresh}) — ratchet the budget down with "
                    "--update-budgets"
                )
        if b.get("fingerprint") and b["fingerprint"] != roll.fingerprint:
            warnings.append(
                f"{name}: program fingerprint changed "
                f"({b['fingerprint']} -> {roll.fingerprint}) — the "
                "entry-point jaxpr was edited; refresh budgets.json if "
                "the metrics above look right"
            )
    for name in committed:
        if name not in rollups and not name.startswith("_"):
            warnings.append(
                f"{name}: committed budget has no live entry point — "
                "remove it with --update-budgets"
            )
    return errors, warnings


def run_cost(
    update: bool = False, budgets_path: Optional[Path] = None, entries=None,
) -> Tuple[List[str], List[str], Dict[str, Rollup], List[Finding]]:
    """The CLI/test driver: trace, roll up, gate (or refresh) budgets.
    Returns (errors, warnings, rollups, findings)."""
    rollups, findings, crashes = collect_rollups(entries)
    errors: List[str] = list(crashes)
    warnings: List[str] = []
    active = [f for f in findings if f.waived is None]
    warnings.extend(str(f) for f in active)
    if update:
        # refresh the ROLLUPS only — a tolerance someone tightened in
        # the committed file must survive the update
        prev_tol = float(
            load_budgets(budgets_path).get("tolerance", DEFAULT_TOLERANCE)
        )
        save_budgets(rollups, budgets_path, tolerance=prev_tol)
    else:
        e, w = check_budgets(rollups, load_budgets(budgets_path))
        errors.extend(e)
        warnings.extend(w)
    return errors, warnings, rollups, findings


# --------------------------------------------------------------------------
# bench hook: production-shaped wave cost
# --------------------------------------------------------------------------


def bench_wave_rollup(
    res: int = 512, spp: int = 256, chunk: int = 1 << 20,
    pool: Optional[int] = None,
) -> Rollup:
    """Static cost of ONE production-shaped drain wave: traces
    PathIntegrator.pool_chunk at the TPU chunk width over a killeroo-like
    scene with the real film resolution (the mesh is kept small — table
    sizes barely touch the per-wave numbers, the wave/film shapes
    dominate). Pure trace: needs no accelerator, which is the point."""
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.scenes import compile_api, make_killeroo_like

    scene, integ = compile_api(make_killeroo_like(
        res=res, spp=spp, integrator="path", maxdepth=5,
        n_theta=24, n_phi=48,
    ))
    film = scene.film
    if pool is None:
        pool = max(chunk // 4, min(chunk, 4096))

    def fn(fs, start_pix, start_s):
        return integ.pool_chunk(
            scene.dev, fs, start_pix, start_s, chunk, pool,
            film=film, cam=scene.camera,
        )

    jx = jax.make_jaxpr(fn)(
        film.init_state(), jnp.int32(0), jnp.int32(0)
    )
    roll, _ = analyze_jaxpr(jx, "bench.pool_chunk", pool)
    return roll


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="python -m tpu_pbrt.analysis.cost")
    ap.add_argument("--bench-wave", action="store_true",
                    help="trace the production-shaped pool wave and print "
                         "its static roofline as one JSON line")
    ap.add_argument("--res", type=int, default=512)
    ap.add_argument("--spp", type=int, default=256)
    ap.add_argument("--update-budgets", action="store_true")
    args = ap.parse_args(argv)
    if args.bench_wave:
        roll = bench_wave_rollup(res=args.res, spp=args.spp)
        line = {
            "static_flops_per_wave": roll.flops,
            "static_bytes_per_wave": roll.hbm_bytes,
            "static_intensity": round(roll.intensity, 3),
            "fingerprint": roll.fingerprint,
        }
        try:
            # the HBM half (hbmcheck, ISSUE 18): the static per-job
            # serve footprint + the fraction of the smallest platform's
            # HBM budget free at current knobs — advisory: the roofline
            # fields above survive any drift
            from tpu_pbrt.analysis.hbmcheck import bench_fields

            line.update(bench_fields(rx=args.res, ry=args.res))
        except Exception as e:  # noqa: BLE001
            import sys

            print(f"bench-wave hbm model failed: {e}", file=sys.stderr)
        print(json.dumps(line))
        return 0
    errors, warnings, rollups, _ = run_cost(update=args.update_budgets)
    for r in rollups.values():
        print(
            f"{r.entry}: {r.flops} flops, {r.hbm_bytes} B, "
            f"intensity {r.intensity:.2f}, fp {r.fingerprint}"
        )
    for w in warnings:
        print(f"WARN: {w}")
    for e in errors:
        print(f"ERROR: {e}")
    return 1 if errors else 0


if __name__ == "__main__":
    import sys

    sys.exit(_main())
