"""pallascheck — static VMEM-budget and grid-semantics verification of
the fused Pallas kernels (analysis layer 5).

The fused wavefront kernels (accel/fusedwave.py) rest on invariants that
lived only as prose until this pass: the VMEM budget math was a module
docstring, the matching TPU_PBRT_FUSED_MAX_RAYS / MAX_NODES caps were
hand-set constants, and the bit-identity proof of the closest-hit merge
explicitly relies on sequential TPU grid order for the constant-index_map
accumulator outputs. Every stage-two megakernel (in-kernel segmented
merge, compaction scatter, BSDF shading) and the quantized-treelet node
format adds more VMEM-resident accumulators resting on the same
assumptions. This pass machine-checks them, one layer below where the
suite stopped: it walks the entry-point jaxprs (audit.py's registry),
extracts every `pallas_call` (grid, BlockSpecs/index_maps, scratch,
dimension semantics) and verifies two things.

**VMEM model.** The exact per-grid-step VMEM footprint per kernel:
operand blocks whose index_map varies across the grid are charged
double-buffered (x2 — Mosaic overlaps the next step's DMA with compute),
constant-index_map blocks stay resident across the whole grid and are
charged once, scratch is charged flat; scalar-prefetch operands live in
SMEM and are reported separately. The rollup is committed to
`tpu_pbrt/analysis/vmem_budgets.json` and gated with the same
10%-tolerance / `--update-budgets` workflow as jaxcost, plus a hard
capacity check against per-platform VMEM with headroom (PC-VMEM). On top
of the gate, `derive_caps()` inverts the model — the footprint is affine
in the wave width R (flush) and the node count N (expand) — so the
maximal safe TPU_PBRT_FUSED_MAX_RAYS / MAX_NODES are *derived* per
platform and the hand-set caps in config.py become a checked consequence
(PC-CAPS) instead of folklore. `python -m tpu_pbrt.analysis.pallascheck
--derive-caps` prints the table.

**Grid-semantics rules**, via abstract interpretation of the kernel-body
jaxpr with intervals over `program_id`:

PC-RACE   an output ref revisited across grid steps (constant index_map
          — the accumulator pattern) while its grid dim is declared
          "parallel": under megacore the two cores interleave grid
          steps and the read-modify-write merge silently races. The
          fused flush's ordered merge is EXACTLY this shape — its grid
          dim must stay "arbitrary" (sequential), which fusedwave now
          declares explicitly.
PC-INIT   a revisited output or scratch ref read before any write that
          provably executes on grid step 0 seeds it — the
          `@pl.when(b == 0)` accumulator seed in `_flush_kernel`;
          deleting it turns the repo gate red with this finding.
PC-OOB    a dynamic in-kernel ref load/store whose index interval
          cannot be proven inside the block shape (the scalar-prefetch-
          meta-driven gathers are the motivating class: their ray ids
          come from HBM, so the kernel must clamp before indexing for
          the proof to close).

Like jaxcost, everything is a pure trace: the gate works with the TPU
down. Deliberate violations go in `WAIVERS` with a written reason.
"""

from __future__ import annotations

import hashlib
import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

# --------------------------------------------------------------------------
# platform model
# --------------------------------------------------------------------------

#: VMEM bytes per TensorCore (the Pallas operating target; see
#: /opt/skills guides — ~16 MB/core across current TPU generations)
VMEM_BYTES: Dict[str, int] = {
    "v4": 16 * 1024 * 1024,
    "v5e": 16 * 1024 * 1024,
    "v5p": 16 * 1024 * 1024,
}
#: fraction of VMEM the model may plan against — the rest stays free for
#: Mosaic's own temporaries (the flush kernel's phi/out4 intermediates),
#: semaphores and compiler slack
VMEM_HEADROOM = 0.85

BUDGETS_PATH = Path(__file__).resolve().parent / "vmem_budgets.json"
DEFAULT_TOLERANCE = 0.10

#: (rule, entry substring, detail substring) -> reason; waived findings
#: stay visible (severity "info") but do not fail the gate
WAIVERS: List[Tuple[str, str, str, str]] = []


def _waiver_for(rule: str, entry: str, detail: str) -> Optional[str]:
    for r, e, d, reason in WAIVERS:
        if r == rule and e in entry and d in detail:
            return reason
    return None


@dataclass(frozen=True)
class PallasFinding:
    rule: str
    entry: str
    kernel: str
    detail: str
    severity: str = "error"
    waived: Optional[str] = None

    def __str__(self) -> str:
        w = f" (waived: {self.waived})" if self.waived else ""
        return (
            f"{self.entry}: {self.rule} [{self.severity}] "
            f"kernel {self.kernel}: {self.detail}{w}"
        )


# --------------------------------------------------------------------------
# pallas_call extraction
# --------------------------------------------------------------------------


@dataclass
class Operand:
    """One kernel ref: a mapped input/output block, a scratch buffer or a
    scalar-prefetch operand."""

    kind: str  # "prefetch" | "in" | "out" | "scratch"
    name: str  # BlockMapping origin / kernel param position
    ref_shape: Tuple[int, ...]  # shape the kernel body indexes
    itemsize: int
    grid_axes: frozenset  # grid axes the index_map output depends on

    @property
    def block_bytes(self) -> int:
        n = 1
        for s in self.ref_shape:
            n *= int(s)
        return n * self.itemsize

    @property
    def bytes_per_step(self) -> int:
        """VMEM charge: double-buffered when the block moves with the
        grid, resident-once when it does not; scratch flat; prefetch is
        SMEM (charged separately)."""
        if self.kind == "prefetch":
            return 0
        if self.kind in ("in", "out") and self.grid_axes:
            return 2 * self.block_bytes
        return self.block_bytes

    @property
    def revisited(self) -> bool:
        """Same block every grid step — the VMEM-resident accumulator
        pattern the grid-semantics rules reason about."""
        return self.kind == "out" and not self.grid_axes


@dataclass
class KernelInfo:
    entry: str
    name: str
    key: str
    grid: Tuple[int, ...]
    dimension_semantics: Tuple[str, ...]
    operands: List[Operand]
    jaxpr: object = field(repr=False, default=None)  # kernel body (open)

    @property
    def grid_steps(self) -> int:
        n = 1
        for g in self.grid:
            n *= max(int(g), 1)
        return n

    @property
    def vmem_bytes(self) -> int:
        return sum(op.bytes_per_step for op in self.operands)

    @property
    def smem_bytes(self) -> int:
        return sum(
            op.block_bytes for op in self.operands if op.kind == "prefetch"
        )

    @property
    def fingerprint(self) -> str:
        h = hashlib.sha256()
        h.update(f"{self.grid}{self.dimension_semantics}".encode())
        for op in self.operands:
            h.update(
                f"{op.kind}{op.ref_shape}{op.itemsize}"
                f"{sorted(op.grid_axes)}".encode()
            )
        return h.hexdigest()[:16]

    def to_json(self) -> Dict:
        return {
            "vmem_bytes_per_step": self.vmem_bytes,
            "smem_bytes": self.smem_bytes,
            "grid_steps": self.grid_steps,
            "fingerprint": self.fingerprint,
        }


def _index_map_grid_axes(bm, n_grid: int) -> frozenset:
    """Grid axes an operand's block index depends on: forward taint of
    the index_map jaxpr from its grid-index invars (invars past n_grid
    are scalar-prefetch operands — a block picked by `m[i, 0]` varies
    with axis i *through* the gather, which the union transfer sees)."""
    from jax.extend import core

    closed = bm.index_map_jaxpr
    jaxpr = closed.jaxpr if isinstance(closed, core.ClosedJaxpr) else closed
    taint: Dict[int, frozenset] = {}
    for k, v in enumerate(jaxpr.invars):
        taint[id(v)] = frozenset([k]) if k < n_grid else frozenset()

    def run(j):
        for eqn in j.eqns:
            t = frozenset()
            for v in eqn.invars:
                if hasattr(v, "count"):  # Var, not Literal
                    t |= taint.get(id(v), frozenset())
            for sub in eqn.params.values():
                for s in _sub_jaxprs(sub):
                    for iv, ov in zip(eqn.invars, s.invars):
                        if hasattr(iv, "count"):
                            taint[id(ov)] = taint.get(id(iv), frozenset())
                    run(s)
                    for sv, ov in zip(s.outvars, eqn.outvars):
                        if hasattr(sv, "count"):
                            t |= taint.get(id(sv), frozenset())
            for v in eqn.outvars:
                taint[id(v)] = taint.get(id(v), frozenset()) | t

    run(jaxpr)
    out = frozenset()
    for v in jaxpr.outvars:
        if hasattr(v, "count"):
            out |= taint.get(id(v), frozenset())
    return out


def _sub_jaxprs(v):
    from tpu_pbrt.analysis.audit import _sub_jaxprs as audit_subs

    return audit_subs(v)


def _ref_shape(aval) -> Tuple[int, ...]:
    return tuple(int(s) for s in getattr(aval, "shape", ()) or ())


def _itemsize(dt) -> int:
    return int(getattr(dt, "itemsize", 4) or 4)


def _dimension_semantics(eqn, n_grid: int) -> Tuple[str, ...]:
    # a mapping {backend: params}; the TPU entry is pltpu.CompilerParams
    cp = eqn.params.get("compiler_params") or {}
    sem = getattr(cp.get("mosaic_tpu"), "dimension_semantics", None)
    if not sem:
        # Mosaic's default for an undeclared dim is "arbitrary"
        # (sequential); fusedwave declares it explicitly so the repo
        # relies on the declaration, not the default
        return ("arbitrary",) * n_grid
    return tuple(
        str(getattr(s, "value", s)) if s else "arbitrary" for s in sem
    )


def extract_kernels(closed_jaxpr, entry: str) -> List[KernelInfo]:
    """Every pallas_call under `closed_jaxpr` (including inside pjit /
    while / cond bodies) as a KernelInfo, in deterministic walk order."""
    from jax.extend import core

    from tpu_pbrt.analysis.audit import iter_jaxprs
    from tpu_pbrt.analysis.cost import block_dims

    infos: List[KernelInfo] = []
    seen: Dict[str, int] = {}
    for j in iter_jaxprs(closed_jaxpr.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name != "pallas_call":
                continue
            gm = eqn.params["grid_mapping"]
            grid = tuple(int(g) for g in (getattr(gm, "grid", ()) or ()))
            n_grid = len(grid)
            n_idx = int(getattr(gm, "num_index_operands", 0) or 0)
            n_out = int(
                getattr(gm, "num_outputs", len(eqn.outvars))
                or len(eqn.outvars)
            )
            bms = list(getattr(gm, "block_mappings", ()) or ())
            n_in = int(getattr(gm, "num_inputs", len(bms) - n_out) or 0)
            n_scr = int(getattr(gm, "num_scratch_operands", 0) or 0)
            kernel = eqn.params.get("jaxpr")
            body = kernel.jaxpr if isinstance(
                kernel, core.ClosedJaxpr
            ) else kernel
            name = eqn.params.get("name") or "kernel"
            invars = list(body.invars) if body is not None else []

            operands: List[Operand] = []
            for k in range(n_idx):
                aval = getattr(invars[k], "aval", None) if k < len(
                    invars
                ) else None
                operands.append(Operand(
                    "prefetch", f"prefetch[{k}]", _ref_shape(aval),
                    _itemsize(getattr(aval, "dtype", None)), frozenset(),
                ))
            for k, bm in enumerate(bms):
                kind = "in" if k < n_in else "out"
                shape = block_dims(bm.block_shape)
                dt = getattr(bm.array_aval, "dtype", None)
                operands.append(Operand(
                    kind, str(getattr(bm, "origin", f"{kind}[{k}]")),
                    shape, _itemsize(dt),
                    _index_map_grid_axes(bm, n_grid),
                ))
            for k in range(n_scr):
                v = invars[n_idx + n_in + n_out + k] if (
                    n_idx + n_in + n_out + k < len(invars)
                ) else None
                aval = getattr(v, "aval", None)
                operands.append(Operand(
                    "scratch", f"scratch[{k}]", _ref_shape(aval),
                    _itemsize(getattr(aval, "dtype", None)), frozenset(),
                ))

            base = f"{entry}::{name}"
            n = seen.get(base, 0)
            seen[base] = n + 1
            infos.append(KernelInfo(
                entry=entry, name=name,
                key=base if n == 0 else f"{base}#{n}",
                grid=grid,
                dimension_semantics=_dimension_semantics(eqn, n_grid),
                operands=operands, jaxpr=body,
            ))
    # a second pallas_call with the same kernel name forces the suffix
    # onto the FIRST occurrence too, so keys stay stable when one is added
    for info in infos:
        if seen.get(f"{info.entry}::{info.name}", 0) > 1 and "#" not in info.key:
            info.key = f"{info.entry}::{info.name}#0"
    return infos


# --------------------------------------------------------------------------
# interval domain for the kernel-body abstract interpreter
# --------------------------------------------------------------------------

_INF = math.inf


class _Iv(tuple):
    """Closed interval [lo, hi] over reals; TOP = (-inf, inf)."""

    __slots__ = ()

    def __new__(cls, lo, hi):
        return super().__new__(cls, (float(lo), float(hi)))

    @property
    def lo(self):
        return self[0]

    @property
    def hi(self):
        return self[1]


_TOP = _Iv(-_INF, _INF)
_BOOL = _Iv(0, 1)


def _iv_join(a: _Iv, b: _Iv) -> _Iv:
    return _Iv(min(a.lo, b.lo), max(a.hi, b.hi))


def _iv_add(a: _Iv, b: _Iv) -> _Iv:
    return _Iv(a.lo + b.lo, a.hi + b.hi)


def _iv_sub(a: _Iv, b: _Iv) -> _Iv:
    return _Iv(a.lo - b.hi, a.hi - b.lo)


def _iv_mul(a: _Iv, b: _Iv) -> _Iv:
    cs = []
    for x in (a.lo, a.hi):
        for y in (b.lo, b.hi):
            if (x in (-_INF, _INF) and y == 0) or (
                y in (-_INF, _INF) and x == 0
            ):
                cs.append(0.0)
            else:
                cs.append(x * y)
    return _Iv(min(cs), max(cs))


def _iv_max(a: _Iv, b: _Iv) -> _Iv:
    return _Iv(max(a.lo, b.lo), max(a.hi, b.hi))


def _iv_min(a: _Iv, b: _Iv) -> _Iv:
    return _Iv(min(a.lo, b.lo), min(a.hi, b.hi))


def _iv_lit(val) -> _Iv:
    import numpy as np

    try:
        arr = np.asarray(val)
        if arr.size == 0 or not np.issubdtype(arr.dtype, np.number):
            return _TOP
        return _Iv(float(arr.min()), float(arr.max()))
    except Exception:  # noqa: BLE001 — non-numeric literal
        return _TOP


# --------------------------------------------------------------------------
# the kernel-body walker (PC-OOB over all grid steps, PC-INIT at step 0)
# --------------------------------------------------------------------------


class _RefState:
    __slots__ = ("name", "shape", "tracked", "init")

    def __init__(self, name: str, shape: Tuple[int, ...],
                 tracked: bool, init: bool):
        self.name = name
        self.shape = shape
        self.tracked = tracked
        self.init = init


class _KernelWalk:
    """One pass over the kernel body. mode="oob": program_id spans the
    full grid and dynamic ref indices are bounds-checked. mode="init":
    program_id is pinned to grid step 0 and revisited-output/scratch
    refs are checked for read-before-seed (must-analysis: a write only
    initializes when it definitely executes and covers the full ref)."""

    def __init__(self, info: KernelInfo, mode: str):
        self.info = info
        self.mode = mode
        self.findings: List[PallasFinding] = []
        self.env: Dict[int, _Iv] = {}
        self.refs: Dict[int, _RefState] = {}
        #: outvars of a swap on a not-yet-seeded tracked ref: the
        #: RETURNED OLD VALUE is uninitialized VMEM — a write is only a
        #: read-before-seed if that value is actually consumed, so the
        #: finding fires at the first USE, not at the swap itself (the
        #: seed is itself a swap whose old value is discarded)
        self._uninit_vals: set = set()

    # -- findings ------------------------------------------------------
    def _emit(self, rule: str, detail: str) -> None:
        waived = _waiver_for(rule, self.info.entry, detail)
        f = PallasFinding(
            rule, self.info.entry, self.info.name, detail,
            severity="info" if waived else "error", waived=waived,
        )
        if f not in self.findings:
            self.findings.append(f)

    # -- env helpers ---------------------------------------------------
    def _read(self, v) -> _Iv:
        if not hasattr(v, "count"):  # Literal
            return _iv_lit(getattr(v, "val", None))
        return self.env.get(id(v), _TOP)

    def _write(self, v, iv: _Iv) -> None:
        self.env[id(v)] = iv

    def _bind_ref(self, inner_v, outer_v) -> None:
        if hasattr(outer_v, "count") and id(outer_v) in self.refs:
            self.refs[id(inner_v)] = self.refs[id(outer_v)]

    # -- the walk ------------------------------------------------------
    def run(self) -> List[PallasFinding]:
        ops = self.info.operands
        invars = list(self.info.jaxpr.invars)
        for v, op in zip(invars, ops):
            tracked = op.revisited or op.kind == "scratch"
            self.refs[id(v)] = _RefState(
                op.name, op.ref_shape, tracked,
                init=not tracked,  # inputs/prefetch arrive DMA'd
            )
        self._eval_body(self.info.jaxpr, definite=True, collect=True)
        return self.findings

    def _eval_body(self, jaxpr, definite: bool, collect: bool) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if (
                self.mode == "init" and collect and self._uninit_vals
                and any(
                    hasattr(v, "count") and id(v) in self._uninit_vals
                    for v in eqn.invars
                )
            ):
                self._emit(
                    "PC-INIT",
                    "a value swapped out of a revisited ref before any "
                    "grid-step-0 write seeds it is consumed — the old "
                    "value is uninitialized VMEM on step 0",
                )
            handler = getattr(self, f"_p_{name}", None)
            if handler is not None:
                handler(eqn, definite, collect)
            elif name in ("cond",):
                self._do_cond(eqn, definite, collect)
            elif name == "scan":
                self._do_scan(eqn, definite, collect)
            elif name == "while":
                self._do_while(eqn, definite, collect)
            elif name in _CALL_LIKE:
                self._do_call(eqn, definite, collect)
            else:
                self._transfer(eqn)

    # -- ref ops -------------------------------------------------------
    def _indexers(self, eqn, n_skip: int):
        """Reconstruct the NDIndexer tuple from the flattened dynamic
        leaves (invars past the ref [and stored value])."""
        import jax

        tree = eqn.params.get("tree")
        if tree is None:
            return None
        leaves = list(eqn.invars[n_skip:])
        try:
            return jax.tree_util.tree_unflatten(tree, leaves)
        except Exception:  # noqa: BLE001 — future indexer pytree drift
            return None

    def _check_bounds(self, st: _RefState, indexers, collect: bool) -> None:
        if self.mode != "oob" or not collect or indexers is None:
            return
        for nd in indexers:
            idx = getattr(nd, "indices", None)
            if idx is None:
                continue
            for d, ix in enumerate(idx):
                if d >= len(st.shape):
                    break
                dim = int(st.shape[d])
                start = getattr(ix, "start", None)
                if start is not None:  # a Slice
                    size = int(getattr(ix, "size", 1) or 1)
                    iv = (
                        _Iv(start, start)
                        if isinstance(start, int)
                        else self._read(start)
                    )
                    lo, hi = iv.lo, iv.hi + (size - 1)
                else:
                    iv = (
                        _Iv(ix, ix) if isinstance(ix, int)
                        else self._read(ix)
                    )
                    lo, hi = iv.lo, iv.hi
                if lo < 0 or hi > dim - 1:
                    shown = (
                        "unbounded" if (lo == -_INF or hi == _INF)
                        else f"[{int(lo)}, {int(hi)}]"
                    )
                    self._emit(
                        "PC-OOB",
                        f"ref {st.name} dim {d}: dynamic index interval "
                        f"{shown} not provably inside [0, {dim - 1}] — "
                        "clamp the index (jnp.clip) before the ref "
                        "access so the in-bounds proof closes",
                    )

    def _full_write(self, st: _RefState, indexers) -> bool:
        if indexers is None:
            return False
        for nd in indexers:
            idx = getattr(nd, "indices", None)
            if idx is None:
                return False
            for d, ix in enumerate(idx):
                dim = int(st.shape[d]) if d < len(st.shape) else 1
                start = getattr(ix, "start", None)
                if start is None:
                    if dim != 1:
                        return False
                    if isinstance(ix, int):
                        if ix != 0:
                            return False
                    else:
                        iv = self._read(ix)
                        if not (iv.lo == iv.hi == 0):
                            return False
                    continue
                size = int(getattr(ix, "size", 0) or 0)
                stride = int(getattr(ix, "stride", 1) or 1)
                if (
                    not isinstance(start, int) or start != 0
                    or size != dim or stride != 1
                ):
                    return False
        return True

    def _ref_read(self, eqn, indexers, collect) -> None:
        st = self.refs.get(id(eqn.invars[0]))
        if st is None:
            self._transfer(eqn)
            return
        self._check_bounds(st, indexers, collect)
        if self.mode == "init" and collect and st.tracked and not st.init:
            self._emit(
                "PC-INIT",
                f"ref {st.name} read before any grid-step-0 write seeds "
                "it — the block is revisited across the grid, so step 0 "
                "reads uninitialized VMEM; add a @pl.when(program_id == "
                "0) seed before the first read",
            )
        for v in eqn.outvars:
            self._write(v, _TOP)

    def _ref_write(self, eqn, indexers, definite, collect) -> None:
        st = self.refs.get(id(eqn.invars[0]))
        if st is None:
            self._transfer(eqn)
            return
        self._check_bounds(st, indexers, collect)
        if self.mode == "init" and st.tracked and not st.init:
            # the old value this swap RETURNS is uninitialized garbage;
            # flag it at its first use (see _uninit_vals)
            for v in eqn.outvars:
                self._uninit_vals.add(id(v))
            if definite and self._full_write(st, indexers):
                st.init = True
        for v in eqn.outvars:
            self._write(v, _TOP)

    def _p_get(self, eqn, definite, collect):
        self._ref_read(eqn, self._indexers(eqn, 1), collect)

    def _p_swap(self, eqn, definite, collect):
        self._ref_write(eqn, self._indexers(eqn, 2), definite, collect)

    def _masked_args(self, eqn):
        """pl.load/pl.swap lower to masked_load/masked_swap whose WHOLE
        arg list (ref, indexer tuple, [value,] mask) flattens through
        params['args_tree']."""
        import jax

        at = eqn.params.get("args_tree")
        if at is None:
            return None
        try:
            return jax.tree_util.tree_unflatten(at, list(eqn.invars))
        except Exception:  # noqa: BLE001 — future layout drift
            return None

    @staticmethod
    def _masked_idx(args):
        if args is not None and len(args) > 1 and isinstance(
            args[1], tuple
        ):
            return args[1]
        return None

    def _p_masked_load(self, eqn, definite, collect):
        args = self._masked_args(eqn)
        self._ref_read(eqn, self._masked_idx(args), collect)

    def _p_masked_swap(self, eqn, definite, collect):
        args = self._masked_args(eqn)
        # a masked store is a PARTIAL write even over full slices: only
        # unmasked lanes are seeded, so it never establishes init
        masked = args is not None and len(args) > 3 and args[3] is not None
        self._ref_write(
            eqn, self._masked_idx(args), definite and not masked, collect
        )

    def _p_addupdate(self, eqn, definite, collect):
        # accumulate = read-modify-write: counts as a read for PC-INIT
        st = self.refs.get(id(eqn.invars[0]))
        if st is None:
            return
        indexers = self._indexers(eqn, 2)
        self._check_bounds(st, indexers, collect)
        if self.mode == "init" and collect and st.tracked and not st.init:
            self._emit(
                "PC-INIT",
                f"ref {st.name} accumulated (addupdate) before any "
                "grid-step-0 write seeds it",
            )

    # -- control flow --------------------------------------------------
    def _do_cond(self, eqn, definite, collect):
        branches = eqn.params["branches"]
        pred = self._read(eqn.invars[0])
        ops = eqn.invars[1:]
        if pred.lo == pred.hi and not math.isinf(pred.lo):
            k = min(max(int(pred.lo), 0), len(branches) - 1)
            self._interp_branch(branches[k], ops, eqn, definite, collect)
            return
        # the join runs over the PRE-cond ref ids only: branch
        # interpretation adds branch-local alias ids for the same
        # _RefState objects, and an id first seen in a later branch is
        # absent from earlier snapshots — joining over it would falsely
        # clear init on a ref seeded before the cond. Every ref object
        # is reachable from its original kernel-invar id, so the
        # saved-id join covers all of them.
        saved = {vid: st.init for vid, st in self.refs.items()}
        states = []
        out_ivs = None
        for br in branches:
            for vid, init in saved.items():
                # reset to the pre-cond state for each branch
                self.refs[vid].init = init
            # a write inside a branch initializes for THAT branch's own
            # later reads (the write dominates them whenever the branch
            # runs at all); the must-join below strips it for code after
            # the cond unless every branch wrote
            ivs = self._interp_branch(br, ops, eqn, definite, collect)
            states.append({vid: self.refs[vid].init for vid in saved})
            out_ivs = ivs if out_ivs is None else [
                _iv_join(a, b) for a, b in zip(out_ivs, ivs)
            ]
        # must-analysis: initialized only if every branch initialized it
        for vid, init in saved.items():
            self.refs[vid].init = all(s.get(vid, init) for s in states)
        for v, iv in zip(eqn.outvars, out_ivs or []):
            self._write(v, iv)

    def _interp_branch(self, closed, ops, eqn, definite, collect):
        from jax.extend import core

        j = closed.jaxpr if isinstance(closed, core.ClosedJaxpr) else closed
        for iv_var, ov in zip(ops, j.invars):
            self._write(ov, self._read(iv_var))
            self._bind_ref(ov, iv_var)
        self._eval_body(j, definite, collect)
        ivs = [self._read(v) for v in j.outvars]
        for v, iv in zip(eqn.outvars, ivs):
            self._write(v, iv)
        return ivs

    def _affine_step(self, body, i_carry: int, n_consts: int) -> Optional[float]:
        """Literal step c when carry #i_carry is `carry + c` (the
        fori_loop counter shape); 0.0 when it passes through unchanged."""
        carry_in = body.invars[n_consts + i_carry]
        out = body.outvars[i_carry]
        if out is carry_in:
            return 0.0
        for eqn in body.eqns:
            if out in eqn.outvars and eqn.primitive.name == "add":
                a, b = eqn.invars
                if a is carry_in and not hasattr(b, "count"):
                    return float(getattr(b, "val", 0))
                if b is carry_in and not hasattr(a, "count"):
                    return float(getattr(a, "val", 0))
        return None

    def _do_scan(self, eqn, definite, collect):
        from jax.extend import core

        p = eqn.params
        closed = p["jaxpr"]
        body = closed.jaxpr if isinstance(
            closed, core.ClosedJaxpr
        ) else closed
        nc = int(p.get("num_consts", 0))
        ncar = int(p.get("num_carry", 0))
        length = max(int(p.get("length", 1) or 1), 1)
        ins = [self._read(v) for v in eqn.invars]
        for iv_var, ov in zip(eqn.invars, body.invars):
            self._bind_ref(ov, iv_var)
        carry = list(ins[nc:nc + ncar])
        # settle the carry intervals over all iterations first
        settled = [None] * ncar
        for i in range(ncar):
            step = self._affine_step(body, i, nc)
            if step is not None:
                total = step * (length - 1)
                settled[i] = _iv_join(
                    carry[i], _iv_add(carry[i], _Iv(total, total))
                )
        if any(s is None for s in settled):
            cur = list(carry)
            for _ in range(3):
                self._bind_scan_env(body, ins, nc, cur)
                self._eval_body(body, False, collect=False)
                new = [self._read(v) for v in body.outvars[:ncar]]
                joined = [_iv_join(a, b) for a, b in zip(cur, new)]
                if joined == cur:
                    break
                cur = joined
            else:
                cur = [_TOP] * ncar  # widen: no convergence in 3 passes
            for i in range(ncar):
                if settled[i] is None:
                    settled[i] = cur[i]
        # one findings pass with the settled intervals; the first
        # iteration is the PC-INIT worst case (init-state only grows)
        self._bind_scan_env(body, ins, nc, settled)
        self._eval_body(body, definite, collect)
        outs = [self._read(v) for v in body.outvars]
        for v, iv in zip(eqn.outvars, settled + outs[ncar:]):
            self._write(v, iv)

    def _bind_scan_env(self, body, ins, nc, carry):
        for k, ov in enumerate(body.invars):
            if k < nc:
                self._write(ov, ins[k])
            elif k < nc + len(carry):
                self._write(ov, carry[k - nc])
            else:
                self._write(ov, ins[k] if k < len(ins) else _TOP)

    def _do_while(self, eqn, definite, collect):
        from jax.extend import core

        p = eqn.params
        cn = int(p.get("cond_nconsts", 0))
        bn = int(p.get("body_nconsts", 0))
        body_c = p["body_jaxpr"]
        body = body_c.jaxpr if isinstance(
            body_c, core.ClosedJaxpr
        ) else body_c
        ins = [self._read(v) for v in eqn.invars]
        carry = list(ins[cn + bn:])
        for iv_var, ov in zip(eqn.invars[cn:], body.invars):
            self._bind_ref(ov, iv_var)
        cur = list(carry)
        for _ in range(3):
            for k, ov in enumerate(body.invars):
                self._write(
                    ov, ins[cn + k] if k < bn else cur[k - bn]
                )
            self._eval_body(body, False, collect=False)
            new = [self._read(v) for v in body.outvars]
            joined = [_iv_join(a, b) for a, b in zip(cur, new)]
            if joined == cur:
                break
            cur = joined
        else:
            cur = [_TOP] * len(carry)
        for k, ov in enumerate(body.invars):
            self._write(ov, ins[cn + k] if k < bn else cur[k - bn])
        # body may run zero times: writes inside never count as seeds
        self._eval_body(body, False, collect)
        for v, iv in zip(eqn.outvars, cur):
            self._write(v, iv)

    def _do_call(self, eqn, definite, collect):
        from jax.extend import core

        sub = None
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            if key in eqn.params:
                sub = eqn.params[key]
                break
        if sub is None:
            self._transfer(eqn)
            return
        inner = sub.jaxpr if isinstance(sub, core.ClosedJaxpr) else sub
        for iv_var, ov in zip(eqn.invars, inner.invars):
            self._write(ov, self._read(iv_var))
            self._bind_ref(ov, iv_var)
        self._eval_body(inner, definite, collect)
        for sv, v in zip(inner.outvars, eqn.outvars):
            self._write(v, self._read(sv))

    # -- interval transfer ---------------------------------------------
    def _transfer(self, eqn) -> None:
        name = eqn.primitive.name
        ins = [self._read(v) for v in eqn.invars]
        out = _TOP
        if name == "program_id":
            ax = int(eqn.params.get("axis", 0))
            hi = self.info.grid[ax] - 1 if ax < len(self.info.grid) else 0
            out = _Iv(0, 0) if self.mode == "init" else _Iv(0, max(hi, 0))
        elif name == "num_programs":
            ax = int(eqn.params.get("axis", 0))
            n = self.info.grid[ax] if ax < len(self.info.grid) else 1
            out = _Iv(n, n)
        elif name == "add":
            out = _iv_add(ins[0], ins[1])
        elif name == "sub":
            out = _iv_sub(ins[0], ins[1])
        elif name == "mul":
            out = _iv_mul(ins[0], ins[1])
        elif name == "neg":
            out = _Iv(-ins[0].hi, -ins[0].lo)
        elif name == "abs":
            lo, hi = ins[0]
            out = _Iv(0 if lo <= 0 <= hi else min(abs(lo), abs(hi)),
                      max(abs(lo), abs(hi)))
        elif name == "max":
            out = _iv_max(ins[0], ins[1])
        elif name == "min":
            out = _iv_min(ins[0], ins[1])
        elif name == "clamp":  # clamp(lo, x, hi)
            out = _iv_max(ins[0], _iv_min(ins[1], ins[2]))
        elif name in ("floor", "ceil", "round"):
            lo, hi = ins[0] if ins else _TOP
            out = _Iv(
                lo if math.isinf(lo) else math.floor(lo),
                hi if math.isinf(hi) else math.ceil(hi),
            )
        elif name == "sign":
            out = _Iv(-1, 1)
        elif name in ("convert_element_type", "reduce_precision", "copy",
                      "stop_gradient"):
            out = ins[0] if ins else _TOP
        elif name in ("reshape", "transpose", "squeeze", "expand_dims",
                      "broadcast_in_dim", "slice", "rev", "reduce_max",
                      "reduce_min", "cummax", "cummin"):
            out = ins[0] if ins else _TOP
        elif name == "concatenate":
            out = ins[0]
            for iv in ins[1:]:
                out = _iv_join(out, iv)
        elif name == "select_n":
            out = ins[1] if len(ins) > 1 else _TOP
            for iv in ins[2:]:
                out = _iv_join(out, iv)
        elif name in ("eq", "ne", "lt", "le", "gt", "ge"):
            out = self._compare(name, ins[0], ins[1])
        elif name in ("and", "or", "not", "xor", "is_finite",
                      "reduce_and", "reduce_or"):
            # [0, 1] is only sound for BOOLEAN logic; the same
            # primitives on integer dtypes are bitwise and stay TOP
            dt = getattr(
                getattr(eqn.outvars[0], "aval", None), "dtype", None
            )
            out = _BOOL if str(dt) == "bool" else _TOP
        elif name in ("iota",):
            dim = int(eqn.params.get("dimension", 0))
            shape = getattr(eqn.outvars[0].aval, "shape", (1,))
            n = int(shape[dim]) if dim < len(shape) else 1
            out = _Iv(0, max(n - 1, 0))
        elif name in ("gather", "dynamic_slice", "take"):
            out = ins[0] if ins else _TOP  # values drawn from the source
        elif name == "shift_right_logical" and len(ins) == 2:
            if ins[0].lo >= 0 and ins[1].lo == ins[1].hi and not math.isinf(
                ins[1].lo
            ):
                s = int(ins[1].lo)
                hi = ins[0].hi if math.isinf(ins[0].hi) else int(
                    ins[0].hi
                ) >> s
                out = _Iv(int(ins[0].lo) >> s, hi)
        elif name == "argmin" or name == "argmax":
            aval = getattr(eqn.invars[0], "aval", None)
            n = 1
            for s in getattr(aval, "shape", ()) or ():
                n *= int(s)
            out = _Iv(0, max(n - 1, 0))
        for v in eqn.outvars:
            self._write(v, out)

    @staticmethod
    def _compare(name: str, a: _Iv, b: _Iv) -> _Iv:
        def known(t, f):  # (provably true, provably false)
            if t:
                return _Iv(1, 1)
            if f:
                return _Iv(0, 0)
            return _BOOL

        if name == "lt":
            return known(a.hi < b.lo, a.lo >= b.hi)
        if name == "le":
            return known(a.hi <= b.lo, a.lo > b.hi)
        if name == "gt":
            return known(a.lo > b.hi, a.hi <= b.lo)
        if name == "ge":
            return known(a.lo >= b.hi, a.hi < b.lo)
        if name == "eq":
            return known(
                a.lo == a.hi == b.lo == b.hi and not math.isinf(a.lo),
                a.hi < b.lo or b.hi < a.lo,
            )
        if name == "ne":
            return known(
                a.hi < b.lo or b.hi < a.lo,
                a.lo == a.hi == b.lo == b.hi and not math.isinf(a.lo),
            )
        return _BOOL


_CALL_LIKE = {"jit", "pjit", "closed_call", "core_call", "xla_call", "remat",
              "checkpoint", "custom_jvp_call", "custom_vjp_call",
              "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"}


# --------------------------------------------------------------------------
# per-kernel checks
# --------------------------------------------------------------------------


def check_kernel(info: KernelInfo) -> List[PallasFinding]:
    """PC-RACE (structural) + PC-OOB/PC-INIT (kernel-body interpretation)
    for one extracted kernel."""
    findings: List[PallasFinding] = []
    for ax, sem in enumerate(info.dimension_semantics):
        if sem != "parallel":
            continue
        for op in info.operands:
            if op.kind == "out" and ax not in op.grid_axes:
                f = PallasFinding(
                    "PC-RACE", info.entry, info.name,
                    f"output {op.name} is revisited across grid dim {ax} "
                    "(constant index_map — the VMEM accumulator pattern) "
                    "but that dim is declared \"parallel\": under "
                    "megacore both cores interleave its steps and the "
                    "read-modify-write merge races; declare the dim "
                    "\"arbitrary\"",
                )
                w = _waiver_for(f.rule, f.entry, f.detail)
                if w:
                    f = PallasFinding(
                        f.rule, f.entry, f.kernel, f.detail, "info", w
                    )
                if f not in findings:
                    findings.append(f)
    if info.jaxpr is not None:
        for mode in ("oob", "init"):
            try:
                findings.extend(_KernelWalk(info, mode).run())
            except Exception as e:  # noqa: BLE001 — report, never raise
                findings.append(PallasFinding(
                    "PC-CRASH", info.entry, info.name,
                    f"{mode} interpretation crashed: "
                    f"{type(e).__name__}: {e}",
                ))
    return findings


# --------------------------------------------------------------------------
# entry points (audit.py's registry — the fused programs)
# --------------------------------------------------------------------------


def default_entry_points():
    """name -> () -> ClosedJaxpr for every entry point that lowers
    through Pallas: the fused stream traversal, the fused pool drain and
    the fused mesh step (flush + both expand variants each)."""
    from tpu_pbrt.analysis import audit

    return {
        "stream_intersect_fused": lambda: audit.stream_traversal_jaxpr(
            fused=True
        ),
        "pool_chunk_fused": lambda: audit.pool_chunk_jaxpr(fused=True),
        "sharded_pool_renderer_fused": lambda: audit.mesh_step_jaxpr(
            fused=True
        ),
    }


def collect_kernels(
    entries=None,
) -> Tuple[Dict[str, KernelInfo], List[PallasFinding], List[str]]:
    """Trace every entry point and extract its kernels. Crashes are
    reported, never raised (the CLI must print a full report). An entry
    with NO pallas_call is itself an error — the fused program silently
    stopped lowering through Pallas and the gate would be vacuous."""
    entries = entries if entries is not None else default_entry_points()
    kernels: Dict[str, KernelInfo] = {}
    findings: List[PallasFinding] = []
    crashes: List[str] = []
    for name, fn in entries.items():
        try:
            jx = fn()
            infos = extract_kernels(jx, name)
        except Exception as e:  # noqa: BLE001
            crashes.append(
                f"{name}: pallascheck trace crashed: {type(e).__name__}: {e}"
            )
            continue
        if not infos:
            crashes.append(
                f"{name}: no pallas_call found — the fused entry point "
                "no longer lowers through Pallas; pallascheck has "
                "nothing to verify"
            )
        for info in infos:
            kernels[info.key] = info
            findings.extend(check_kernel(info))
    return kernels, findings, crashes


# --------------------------------------------------------------------------
# the VMEM budget gate (same workflow as jaxcost's budgets.json)
# --------------------------------------------------------------------------


def load_budgets(path: Optional[Path] = None) -> Dict:
    p = Path(path) if path is not None else BUDGETS_PATH
    if not p.exists():
        return {"tolerance": DEFAULT_TOLERANCE, "entries": {}}
    return json.loads(p.read_text())


def save_budgets(
    kernels: Dict[str, KernelInfo], path: Optional[Path] = None,
    tolerance: float = DEFAULT_TOLERANCE,
) -> Path:
    import jax

    p = Path(path) if path is not None else BUDGETS_PATH
    data = {
        "_comment": (
            "Per-kernel static VMEM footprints (pallascheck, ISSUE 11). "
            "bytes_per_step = double-buffered moving blocks + resident "
            "constant-index_map blocks + flat scratch. Regenerate with "
            "`python -m tpu_pbrt.analysis --update-budgets` after an "
            "INTENTIONAL kernel change; CI fails when a kernel's "
            "footprint drifts past tolerance or any kernel exceeds "
            "platform VMEM with headroom."
        ),
        "tolerance": tolerance,
        "vmem_headroom": VMEM_HEADROOM,
        "jax_version": jax.__version__,
        "entries": {k: i.to_json() for k, i in sorted(kernels.items())},
    }
    p.write_text(json.dumps(data, indent=2, sort_keys=True) + "\n")
    return p


def check_budgets(
    kernels: Dict[str, KernelInfo], budgets: Dict
) -> Tuple[List[str], List[str]]:
    errors: List[str] = []
    warnings: List[str] = []
    tol = float(budgets.get("tolerance", DEFAULT_TOLERANCE))
    committed = budgets.get("entries", {})
    for key, info in sorted(kernels.items()):
        b = committed.get(key)
        if b is None:
            errors.append(
                f"{key}: no committed VMEM budget — run "
                "`python -m tpu_pbrt.analysis --update-budgets` and "
                "commit vmem_budgets.json"
            )
            continue
        base = int(b.get("vmem_bytes_per_step", 0))
        if base > 0:
            ratio = info.vmem_bytes / base
            if ratio > 1.0 + tol:
                errors.append(
                    f"{key}: static VMEM/step regressed {ratio:.2f}x "
                    f"({base} -> {info.vmem_bytes} B, tolerance "
                    f"{tol:.0%}) — shrink the kernel or, if intentional, "
                    "refresh with --update-budgets"
                )
            elif ratio < 1.0 - tol:
                warnings.append(
                    f"{key}: static VMEM/step improved {ratio:.2f}x "
                    f"({base} -> {info.vmem_bytes} B) — ratchet with "
                    "--update-budgets"
                )
        if b.get("fingerprint") and b["fingerprint"] != info.fingerprint:
            warnings.append(
                f"{key}: kernel structure fingerprint changed "
                f"({b['fingerprint']} -> {info.fingerprint}) — refresh "
                "vmem_budgets.json if the footprint above looks right"
            )
    for key in committed:
        if key not in kernels and not key.startswith("_"):
            warnings.append(
                f"{key}: committed VMEM budget has no live kernel — "
                "remove it with --update-budgets"
            )
    return errors, warnings


def check_capacity(
    kernels: Dict[str, KernelInfo], headroom: float = VMEM_HEADROOM,
) -> List[str]:
    """PC-VMEM: every kernel's per-step footprint must fit the smallest
    platform VMEM with headroom — statically, before any TPU sees it."""
    errors: List[str] = []
    platform, cap = min(VMEM_BYTES.items(), key=lambda kv: kv[1])
    budget = int(cap * headroom)
    for key, info in sorted(kernels.items()):
        if info.vmem_bytes > budget:
            errors.append(
                f"{key}: PC-VMEM static footprint {info.vmem_bytes} B "
                f"per grid step exceeds {budget} B "
                f"({headroom:.0%} of {platform} VMEM {cap} B) — shrink "
                "the block shapes or lower the fused caps"
            )
    return errors


# --------------------------------------------------------------------------
# cap derivation: invert the affine VMEM model for the fused kernels
# --------------------------------------------------------------------------


def _flush_kernel_info(R: int, L: Optional[int] = None,
                       motion: bool = False, CH: int = 8) -> KernelInfo:
    """Extract the fused flush kernel at wave width R via an abstract
    trace (ShapeDtypeStruct avals — no allocation, works at R = 2^22)."""
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.accel import fusedwave
    from tpu_pbrt.accel.stream import STREAM_LEAF_TRIS

    L = int(L or STREAM_LEAF_TRIS)
    F = 64 if motion else 16
    s = jax.ShapeDtypeStruct
    jx = jax.make_jaxpr(
        lambda ft, m, rr, rf, t, p: fusedwave.fused_flush_chunk(
            ft, m, rr, rf, t, p, interpret=True
        )
    )(
        s((2, F, 4 * L), jnp.float32), s((CH, 8), jnp.int32),
        s((CH, fusedwave.BLOCK), jnp.int32), s((8, R), jnp.float32),
        s((R,), jnp.float32), s((R,), jnp.int32),
    )
    return extract_kernels(jx, "derive.flush")[0]


def _expand_kernel_info(R: int, N: int, use_onehot: bool,
                        any_hit: bool) -> KernelInfo:
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.accel import fusedwave

    S = 2 * fusedwave.EXPAND_TILE
    s = jax.ShapeDtypeStruct
    tab = s((64, N), jnp.float32) if use_onehot else None
    box = None if use_onehot else s((48, N), jnp.float32)
    cid = None if use_onehot else s((8, N), jnp.int32)
    jx = jax.make_jaxpr(
        lambda k, n, re, pr, t, b, c: fusedwave.fused_expand(
            k, n, re, pr, t, b, c, tb=8, use_onehot=use_onehot,
            any_hit=any_hit, interpret=True,
        )
    )(
        s((S,), jnp.int32), s((S,), jnp.int32), s((8, R), jnp.float32),
        s((R,), jnp.int32), tab, box, cid,
    )
    return extract_kernels(jx, "derive.expand")[0]


def _affine_fit(f, x1: int, x2: int) -> Tuple[int, int]:
    """(intercept a, slope b) of the exactly-affine footprint f(x)."""
    y1, y2 = f(x1), f(x2)
    b = (y2 - y1) // (x2 - x1)
    return y1 - b * x1, b


def _pow2_floor(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


def derive_caps(headroom: float = VMEM_HEADROOM) -> Dict:
    """Invert the VMEM model: per platform, the maximal wave width R the
    fused flush fits (worst case over motion features), then the maximal
    node count N the fused expand fits at the CONFIGURED rays cap (worst
    variant: any-hit, and the node representation the stream tracer
    would pick at that size). The hand-set config.py caps are validated
    against these (PC-CAPS) — the caps are a consequence of the model,
    not folklore."""
    from tpu_pbrt.accel.stream import _ONEHOT_MAX_NODES
    from tpu_pbrt.config import cfg

    r1, r2 = 1 << 12, 1 << 13
    fits = {}
    for motion in (False, True):
        a, b = _affine_fit(
            lambda R, m=motion: _flush_kernel_info(R, motion=m).vmem_bytes,
            r1, r2,
        )
        fits[motion] = (a, b)

    R_op = int(cfg.fused_max_rays)

    def expand_fit(use_onehot: bool, n1: int, n2: int):
        return _affine_fit(
            lambda N: _expand_kernel_info(
                R_op, N, use_onehot=use_onehot, any_hit=True
            ).vmem_bytes,
            n1, n2,
        )

    # primary fit in the box48 regime (every candidate cap above the
    # one-hot cutoff compiles the (48,N)+(8,N) tables); the one-hot
    # refit below only runs when the derived cap lands UNDER the cutoff
    ea, eb = expand_fit(False, 1 << 10, 1 << 11)
    onehot_fit = None

    out: Dict = {
        "headroom": headroom,
        "configured": {
            "fused_max_rays": R_op,
            "fused_max_nodes": int(cfg.fused_max_nodes),
        },
        "platforms": {},
    }
    for platform, cap in sorted(VMEM_BYTES.items()):
        budget = int(cap * headroom)
        rays_raw = min(
            (budget - a) // b for a, b in fits.values() if b > 0
        )
        nodes_raw = (budget - ea) // eb if eb > 0 else 0
        # a box48-regime cap at or below the one-hot cutoff means the
        # whole usable range compiles the (denser-padded) one-hot table
        # instead — re-derive there so the number matches what would
        # really compile, clamped to the cutoff where the
        # representation switches back
        if nodes_raw <= _ONEHOT_MAX_NODES and bool(cfg.onehot):
            if onehot_fit is None:
                onehot_fit = expand_fit(True, 128, 256)
            ea2, eb2 = onehot_fit
            nodes_raw = min(
                (budget - ea2) // eb2 if eb2 > 0 else 0,
                _ONEHOT_MAX_NODES,
            )
        out["platforms"][platform] = {
            "vmem_bytes": cap,
            "budget_bytes": budget,
            "max_rays": int(max(rays_raw, 0)),
            "max_rays_pow2": _pow2_floor(max(rays_raw, 1)),
            "max_nodes": int(max(nodes_raw, 0)),
            "max_nodes_pow2": _pow2_floor(max(nodes_raw, 1)),
            "flush_bytes_per_ray": int(min(b for _, b in fits.values())),
            "expand_bytes_per_node": int(eb),
        }
    return out


def check_caps(derived: Optional[Dict] = None) -> List[str]:
    """PC-CAPS: the configured TPU_PBRT_FUSED_MAX_RAYS / MAX_NODES must
    not exceed what the VMEM model proves safe on the smallest
    platform."""
    errors: List[str] = []
    d = derived if derived is not None else derive_caps()
    worst_rays = min(p["max_rays"] for p in d["platforms"].values())
    worst_nodes = min(p["max_nodes"] for p in d["platforms"].values())
    cfg_rays = d["configured"]["fused_max_rays"]
    cfg_nodes = d["configured"]["fused_max_nodes"]
    if cfg_rays > worst_rays:
        errors.append(
            f"PC-CAPS: TPU_PBRT_FUSED_MAX_RAYS={cfg_rays} exceeds the "
            f"model-safe maximum {worst_rays} "
            f"(pow2 {_pow2_floor(max(worst_rays, 1))}) — waves at the "
            "cap would overflow VMEM; lower the cap or shrink the flush "
            "kernel"
        )
    if cfg_nodes > worst_nodes:
        errors.append(
            f"PC-CAPS: TPU_PBRT_FUSED_MAX_NODES={cfg_nodes} exceeds the "
            f"model-safe maximum {worst_nodes} "
            f"(pow2 {_pow2_floor(max(worst_nodes, 1))}) at the "
            "configured rays cap — lower the cap or shrink the expand "
            "kernel's node tables"
        )
    return errors


def wave_vmem(R: int, n_nodes: int, motion: bool = False,
              L: Optional[int] = None) -> int:
    """Max per-grid-step VMEM footprint across the fused kernels a wave
    of R rays over an n_nodes top tree (L-triangle leaves) would
    dispatch — the `static_vmem_per_wave` bench field (cost.py
    --bench-wave)."""
    from tpu_pbrt.accel.stream import _ONEHOT_MAX_NODES
    from tpu_pbrt.config import cfg

    R = max(int(R), 1)
    n_nodes = max(int(n_nodes), 1)
    onehot = bool(cfg.onehot) and n_nodes <= _ONEHOT_MAX_NODES
    return max(
        _flush_kernel_info(R, L=L, motion=motion).vmem_bytes,
        _expand_kernel_info(R, n_nodes, onehot, any_hit=False).vmem_bytes,
        _expand_kernel_info(R, n_nodes, onehot, any_hit=True).vmem_bytes,
    )


# --------------------------------------------------------------------------
# suite driver
# --------------------------------------------------------------------------


def run_pallascheck(
    update: bool = False, budgets_path: Optional[Path] = None,
    entries=None, check_caps_too: Optional[bool] = None,
) -> Tuple[List[str], List[str]]:
    """CLI/test driver. Returns (errors, warnings). Caps derivation runs
    by default only for the full registry (tests passing a single entry
    skip the extra synthetic traces unless they opt in)."""
    kernels, findings, crashes = collect_kernels(entries)
    errors: List[str] = list(crashes)
    warnings: List[str] = []
    errors.extend(
        str(f) for f in findings if f.severity == "error" and not f.waived
    )
    warnings.extend(str(f) for f in findings if f.waived)
    errors.extend(check_capacity(kernels))
    if update:
        prev_tol = float(
            load_budgets(budgets_path).get("tolerance", DEFAULT_TOLERANCE)
        )
        save_budgets(kernels, budgets_path, tolerance=prev_tol)
    else:
        e, w = check_budgets(kernels, load_budgets(budgets_path))
        errors.extend(e)
        warnings.extend(w)
    if check_caps_too is None:
        check_caps_too = entries is None
    if check_caps_too:
        try:
            errors.extend(check_caps())
        except Exception as e:  # noqa: BLE001
            errors.append(
                f"PC-CAPS derivation crashed: {type(e).__name__}: {e}"
            )
    return errors, warnings


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m tpu_pbrt.analysis.pallascheck"
    )
    ap.add_argument(
        "--derive-caps", action="store_true",
        help="print the maximal safe TPU_PBRT_FUSED_MAX_RAYS/MAX_NODES "
             "per platform VMEM size, derived from the kernel VMEM "
             "model (the source of truth behind the config.py defaults)",
    )
    ap.add_argument("--update-budgets", action="store_true")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)
    if args.derive_caps:
        if args.update_budgets:
            # honor BOTH flags in one shot: refresh the committed
            # budgets first, then print the derived caps — silently
            # ignoring the refresh would leave the gate red after an
            # operator believed they ratified the change
            run_pallascheck(update=True)
            print(f"pallascheck: VMEM budgets refreshed -> {BUDGETS_PATH}")
        d = derive_caps()
        if args.format == "json":
            print(json.dumps(d, indent=2, sort_keys=True))
        else:
            c = d["configured"]
            print(
                f"configured: fused_max_rays={c['fused_max_rays']} "
                f"fused_max_nodes={c['fused_max_nodes']} "
                f"(headroom {d['headroom']:.0%})"
            )
            for platform, p in sorted(d["platforms"].items()):
                dr = p["max_rays_pow2"] - c["fused_max_rays"]
                dn = p["max_nodes_pow2"] - c["fused_max_nodes"]
                print(
                    f"{platform}: VMEM {p['vmem_bytes']} B -> budget "
                    f"{p['budget_bytes']} B; max_rays {p['max_rays']} "
                    f"(pow2 {p['max_rays_pow2']}, delta {dr:+d}), "
                    f"max_nodes {p['max_nodes']} "
                    f"(pow2 {p['max_nodes_pow2']}, delta {dn:+d}); "
                    f"{p['flush_bytes_per_ray']} B/ray flush, "
                    f"{p['expand_bytes_per_node']} B/node expand"
                )
        ok = not check_caps(d)
        return 0 if ok else 1
    errors, warnings = run_pallascheck(update=args.update_budgets)
    for w in warnings:
        print(f"WARN: {w}")
    for e in errors:
        print(f"ERROR: {e}")
    if args.update_budgets:
        print(f"pallascheck: VMEM budgets refreshed -> {BUDGETS_PATH}")
    if not errors:
        print("pallascheck: clean")
    return 1 if errors else 0


if __name__ == "__main__":
    import sys

    from tpu_pbrt.analysis.__main__ import _setup_jax_env

    _setup_jax_env()
    sys.exit(_main())
