"""Analysis layer 5: protocheck — serve/dispatch protocol verification.

Layers 1-4 (jaxlint, jaxpr audit, cost model, shardcheck) verify the
COMPILED side of the renderer: traced programs, budgets, sharding.
This layer verifies the HOST side — the
serve/dispatch protocol itself: the state machine formed by
``serve/service.py`` (job lifecycle + recovery ladder),
``serve/queue.py`` (WFQ policy), and ``integrators/common.py``'s
``DispatchWindow`` (pipelined in-flight slices + deferred checkpoint
writes). Four seeded bugs anchor it, each a named mutant in the
regression corpus (``MUTATION_CASES``):

- **PR-13 clock double-sample wedge** — ``step()`` sampled the wall
  clock once for the runnable filter and again for the backoff-wait
  computation; a ``not_before`` deadline falling between the samples was
  excluded from BOTH, so ``step()`` answered None with work still
  pending. SV-CLOCK codifies the fix; the ``clock-double-sample``
  mutant reproduces the wedge deterministically under a VirtualClock.
- **PR-6 WFQ banked credit** — an idle tenant kept its stale low vtime
  and re-entered monopolizing the mesh. ``reenter()``'s busy clamp is
  the fix; the ``wfq-banked-credit`` mutant removes it and the
  PROTO-VTIME invariant catches the regression at the submit boundary.
- **superseded-deferred-write replay** — a cadence checkpoint deferred
  into the dispatch window must land exactly once or be provably
  superseded (a park/finalize write at the same path with a newer
  cursor); replaying it after the park regresses the durable cursor.
  PROTO-DEFER watches ``parallel/checkpoint``'s write-observer seam;
  the ``defer-replay-after-park`` mutant replays a captured deferred
  write and is flagged by cursor regression.
- **park-path HBM leak (ISSUE 18)** — a park that writes the durable
  emergency checkpoint but skips the film release strands one
  film-state carry in HBM per preemption. PROTO-HBM evaluates
  hbmcheck's (layer 6) memory model on the live service after every
  decision: the watermark must stay under the scenario's static worst
  case, parked/terminal jobs must hold no device buffers, and the
  model must return to baseline at drain. The
  ``park-skips-film-release`` mutant reintroduces the leak.

Two halves:

1. **SV static lint** (``sv_lint_source`` / ``sv_lint_tree``) — AST
   rules over the protocol modules, wired into
   ``python -m tpu_pbrt.analysis`` like every other layer (same
   ``Violation`` dataclass, same ``# jaxlint: disable=`` pragma
   grammar):

   - SV-CLOCK: direct wall-clock calls in clock-scoped modules (the
     injected ``Clock`` seam is the only sanctioned time source), and
     — in ``serve/service.py`` — any step-scoped function that reasons
     about runnability/backoff deadlines yet samples the decision
     clock more than once.
   - SV-DEFER: a ``window.defer(...)`` call without its retirement
     cursor binding, or a durable checkpoint write in the same
     function as a non-discarding window flush (the double-write
     shape the replay mutant exploits).
   - SV-VTIME: a write to ``TenantShare.vtime`` anywhere outside
     ``FairScheduler._set_vtime`` (a fair-share policy bypass).

2. **Protocol model** (``ProtocolModel``) — the REAL ``RenderService``
   run against stub chunk dispatches under a ``VirtualClock``
   (``utils/clock.py``), so a whole service run (submit / step /
   preempt / resume / cancel, window launch / retire / defer, backoff
   deadlines, CHAOS fault firings) is a pure deterministic function of
   an explicit decision sequence. ``tools/explore.py`` enumerates
   decision sequences over this model (bounded DPOR-style search) and
   checks the PROTO-* invariants after every decision. Nothing here
   touches the compiled programs: with the explorer unarmed the
   service, the recorders and every analysis budget are byte-identical
   to the pre-layer-6 tree (the seam defaults to the wall clock).
"""

from __future__ import annotations

import ast
import os
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from tpu_pbrt.analysis.lint import Violation, _PRAGMA_FILE_RE, _PRAGMA_RE

# --------------------------------------------------------------------------
# SV rules (static half)
# --------------------------------------------------------------------------

SV_RULES: Dict[str, str] = {
    "SV-PARSE": "protocol module does not parse",
    "SV-CLOCK": (
        "wall clock sampled outside the injected Clock seam, or a "
        "deadline-scoped function sampling the decision clock twice"
    ),
    "SV-DEFER": (
        "deferred checkpoint write created without a retirement cursor "
        "binding, or combined with a non-discarding window flush"
    ),
    "SV-VTIME": (
        "tenant vtime written outside FairScheduler._set_vtime"
    ),
}

#: modules where ANY direct `time.*` call is a policy bypass — the
#: service and the queue policy must consume only the injected clock
#: (queue.py consumes none at all: `pick` is clock-free by contract)
_CLOCK_SCOPED = (
    "tpu_pbrt/serve/service.py",
    "tpu_pbrt/serve/queue.py",
    "tpu_pbrt/serve/residency.py",
    "tpu_pbrt/fleet/router.py",
)
#: (module, class) pairs clock-scoped at class granularity — the rest
#: of the module legitimately times host work with the stdlib
_CLOCK_SCOPED_CLASSES = (
    ("tpu_pbrt/integrators/common.py", "DispatchWindow"),
)
#: modules where `.defer(` means DispatchWindow.defer
_DEFER_SCOPED = (
    "tpu_pbrt/serve/service.py",
    "tpu_pbrt/serve/__main__.py",
    "tpu_pbrt/integrators/common.py",
)
_TIME_ATTRS = frozenset(
    ("time", "monotonic", "perf_counter", "sleep", "time_ns",
     "monotonic_ns", "perf_counter_ns")
)
#: attribute names that count as a DECISION sample of the clock
_SAMPLE_ATTRS = frozenset(("_now", "now"))


def _pragma_lines(src: str) -> Tuple[Dict[int, set], set]:
    """(lineno -> disabled rules, file-level disabled rules) — the same
    `# jaxlint: disable=` grammar layer 1 uses, so one suppression
    idiom covers every analysis layer."""
    per_line: Dict[int, set] = {}
    file_wide: set = set()
    for i, line in enumerate(src.splitlines(), 1):
        m = _PRAGMA_FILE_RE.search(line)
        if m:
            file_wide |= {r.strip() for r in m.group(1).split(",")}
        m = _PRAGMA_RE.search(line)
        if m:
            per_line.setdefault(i, set()).update(
                r.strip() for r in m.group(1).split(",")
            )
    return per_line, file_wide


def _shallow_walk(node: ast.AST):
    """Yield `node`'s body nodes without descending into nested
    function/lambda scopes — SV-CLOCK's one-sample-per-scope contract
    is per function, and a deferred `write()` closure is its own
    scope."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))


def _is_time_call(node: ast.AST) -> Optional[str]:
    if (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id == "time"
        and node.func.attr in _TIME_ATTRS
    ):
        return node.func.attr
    return None


class _SvVisitor(ast.NodeVisitor):
    def __init__(self, rel: str):
        self.rel = rel
        self.out: List[Violation] = []
        self.class_stack: List[str] = []
        self.fn_stack: List[ast.FunctionDef] = []

    # -- helpers -----------------------------------------------------------
    def _emit(self, rule: str, line: int, msg: str) -> None:
        self.out.append(Violation(rule, self.rel, line, msg, "error"))

    def _in_clock_scope(self) -> bool:
        if self.rel in _CLOCK_SCOPED:
            return True
        for mod, cls in _CLOCK_SCOPED_CLASSES:
            if self.rel == mod and cls in self.class_stack:
                return True
        return False

    # -- structure ---------------------------------------------------------
    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.fn_stack.append(node)
        if self.rel == "tpu_pbrt/serve/service.py":
            self._check_double_sample(node)
        self.generic_visit(node)
        self.fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _check_double_sample(self, fn: ast.FunctionDef) -> None:
        """SV-CLOCK's second aspect: a function that reasons about
        runnability or backoff deadlines (references `not_before` or
        calls `_runnable`) must sample the decision clock at most once
        and thread that value through — the PR-13 wedge was exactly a
        second sample racing a deadline between the two."""
        deadline_scoped = False
        samples: List[int] = []
        for n in _shallow_walk(fn):
            if isinstance(n, ast.Attribute) and n.attr == "not_before":
                deadline_scoped = True
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
                if n.func.attr == "_runnable":
                    deadline_scoped = True
                if n.func.attr in _SAMPLE_ATTRS:
                    samples.append(n.lineno)
            if _is_time_call(n):
                samples.append(n.lineno)
        if deadline_scoped and len(samples) > 1:
            self._emit(
                "SV-CLOCK", sorted(samples)[1],
                f"{fn.name}() reasons about backoff deadlines but samples "
                f"the decision clock {len(samples)} times (lines "
                f"{sorted(samples)}); sample once and thread the value",
            )

    # -- leaf rules ---------------------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        attr = _is_time_call(node)
        if attr is not None and self._in_clock_scope():
            self._emit(
                "SV-CLOCK", node.lineno,
                f"direct wall-clock call time.{attr}() in a clock-scoped "
                "module; route through the injected Clock (utils/clock.py)",
            )
        if (
            self.rel in _DEFER_SCOPED
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "defer"
        ):
            kw = {k.arg for k in node.keywords}
            if len(node.args) < 2 and not ({"cursor", "fn"} <= kw):
                self._emit(
                    "SV-DEFER", node.lineno,
                    "defer() without a retirement cursor binding — a "
                    "deferred write must be tied to the slice whose "
                    "retirement runs it",
                )
        self.generic_visit(node)

    def _check_vtime_target(self, target: ast.AST, line: int) -> None:
        if not (isinstance(target, ast.Attribute) and target.attr == "vtime"):
            return
        sanctioned = (
            self.rel == "tpu_pbrt/serve/queue.py"
            and "FairScheduler" in self.class_stack
            and bool(self.fn_stack)
            and self.fn_stack[-1].name == "_set_vtime"
        )
        if not sanctioned:
            self._emit(
                "SV-VTIME", line,
                "vtime written outside FairScheduler._set_vtime — the "
                "fair-share invariants live in its three sanctioned "
                "callers; use the policy API",
            )

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._check_vtime_target(t, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_vtime_target(node.target, node.lineno)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        self._check_vtime_target(node.target, node.lineno)
        self.generic_visit(node)


def _check_flush_after_write(tree: ast.Module, rel: str) -> List[Violation]:
    """SV-DEFER's second aspect (service.py only): a function that both
    writes a durable checkpoint and drains (rather than discards) a
    dispatch window can replay a superseded deferred write — the exact
    regression the `defer-replay-after-park` mutant seeds."""
    if rel != "tpu_pbrt/serve/service.py":
        return []
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        saves: List[int] = []
        drains: List[int] = []
        for n in _shallow_walk(node):
            if not isinstance(n, ast.Call):
                continue
            fname = (
                n.func.attr if isinstance(n.func, ast.Attribute)
                else n.func.id if isinstance(n.func, ast.Name) else ""
            )
            if fname == "save_checkpoint":
                saves.append(n.lineno)
            if fname in ("flush", "drain"):
                discard = next(
                    (k.value for k in n.keywords if k.arg == "discard"),
                    None,
                )
                if fname == "drain" or not (
                    isinstance(discard, ast.Constant)
                    and discard.value is True
                ):
                    drains.append(n.lineno)
        if saves and drains:
            out.append(Violation(
                "SV-DEFER", rel, drains[0],
                f"{node.name}() both writes a checkpoint (line {saves[0]}) "
                "and drains a dispatch window without discard=True — the "
                "drained deferred writes would replay a superseded cursor",
                "error",
            ))
    return out


def sv_lint_source(src: str, rel: str) -> List[Violation]:
    """Run the SV rules over one module's source. `rel` is the
    repo-relative posix path (the scoping key)."""
    per_line, file_wide = _pragma_lines(src)
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Violation(
            "SV-PARSE", rel, e.lineno or 0, f"does not parse: {e.msg}",
            "error",
        )]
    visitor = _SvVisitor(rel)
    visitor.visit(tree)
    found = visitor.out + _check_flush_after_write(tree, rel)
    # def-line pragmas cover their function body (the per-function
    # SV-CLOCK aspect reports at the offending sample, which may be far
    # from where the waiver is naturally written)
    def_spans: List[Tuple[int, int, int]] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            def_spans.append(
                (node.lineno, getattr(node, "end_lineno", node.lineno),
                 node.lineno)
            )
    out: List[Violation] = []
    for v in found:
        if v.rule in file_wide:
            continue
        if v.rule in per_line.get(v.line, ()):
            continue
        covered = any(
            v.rule in per_line.get(dl, ())
            for lo, hi, dl in def_spans
            if lo <= v.line <= hi
        )
        if not covered:
            out.append(v)
    return sorted(out, key=lambda v: (v.path, v.line, v.rule))


def sv_lint_file(path: str, rel: str) -> List[Violation]:
    with open(path, encoding="utf-8") as f:
        return sv_lint_source(f.read(), rel)


def sv_lint_tree(root: Optional[str] = None) -> List[Violation]:
    """Lint the whole `tpu_pbrt` package under `root` (default: the
    installed tree this module came from). SV-VTIME is global — a
    policy bypass can hide anywhere — while the clock/defer scopes are
    keyed by the repo-relative path."""
    if root is None:
        root = repo_root()
    pkg = os.path.join(root, "tpu_pbrt")
    out: List[Violation] = []
    for dirpath, _dirnames, filenames in os.walk(pkg):
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            out.extend(sv_lint_file(path, rel))
    return sorted(out, key=lambda v: (v.path, v.line, v.rule))


def repo_root() -> str:
    """The checkout root (tpu_pbrt/analysis/protocheck.py -> up 3)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))


# --------------------------------------------------------------------------
# Stub harness (dynamic half) — real service, stub chunk dispatches
# --------------------------------------------------------------------------

#: every stub chunk reports exactly this many rays — the counter-
#: reconciliation invariant (PROTO-COUNT) is then n_chunks * this
RAYS_PER_CHUNK = 64

_HARNESS: Optional[Dict[str, Any]] = None


def _harness() -> Dict[str, Any]:
    """Build (once) the stub scene/plan/integrator classes. Lazy and
    cached: importing protocheck for the SV lint must not import jax —
    the analysis runner's `need_jax` gating decides when the dynamic
    half may load."""
    global _HARNESS
    if _HARNESS is not None:
        return _HARNESS
    import zlib

    import numpy as np

    from tpu_pbrt.core.film import FilmState
    from tpu_pbrt.integrators.common import WavefrontIntegrator

    class StubFilm:
        """2x2 film with the real FilmState layout; develop() mirrors
        the radiance/weight normalization shape deterministically."""

        full_resolution = (2, 2)

        def init_state(self):
            return FilmState(
                rgb=np.zeros((2, 2, 3), np.float32),
                weight=np.zeros((2, 2), np.float32),
                splat=np.zeros((2, 2, 3), np.float32),
            )

        def develop(self, state, splat_scale: float = 1.0):
            w = np.maximum(np.asarray(state.weight), 1e-9)[..., None]
            return np.asarray(state.rgb) / w + np.asarray(
                state.splat
            ) * np.float32(splat_scale)

    class StubScene:
        def __init__(self):
            self.dev: Dict[str, Any] = {}  # no HBM-resident tables
            self.film = StubFilm()

    def _contrib(c: int) -> Any:
        # distinct deterministic per-chunk deposit: accumulation-order
        # bugs change the film bit pattern even on a 2x2 stub
        val = (zlib.crc32(f"chunk:{c}".encode()) % 1021) / 1021.0
        return np.full((2, 2, 3), np.float32(val), np.float32)

    class StubPlan:
        """Duck-typed ChunkPlan: every field/method the service touches,
        with dispatch() a pure numpy accumulate — idempotent, instant,
        and bit-deterministic, so film identity across interleavings is
        checkable exactly."""

        def __init__(self, n_chunks: int, depth: int):
            self.n_chunks = int(n_chunks)
            self.pipeline_depth = max(1, int(depth))
            self.spp = 1
            self.film = StubFilm()
            self.scene = StubScene()  # (_finalize asks it for "tstream")
            self.fingerprint = f"stub:n{n_chunks}:d{depth}"
            self.tracer = "stub"
            self.use_regen = False
            self.pool = 1

        def capacity_audit(self) -> None:
            pass

        def dispatch(self, state, c: int):
            state2 = FilmState(
                rgb=state.rgb + _contrib(c),
                weight=state.weight + np.float32(1.0),
                splat=state.splat,
            )
            return state2, np.int64(RAYS_PER_CHUNK)

        def aux_parts(self, aux):
            return (aux, None, None, None, None)

    class StubIntegrator(WavefrontIntegrator):
        """Subclasses the real base WITHOUT overriding render() — the
        submit-time chunked-loop check must accept it via the real
        entry point — and with its own tiny ctor (no scene plumbing)."""

        def __init__(self, n_chunks: int, depth: int):  # noqa: D107
            self.n_chunks = int(n_chunks)
            self.depth = int(depth)
            self.name = "stub"

        def prepare_chunks(self, scene=None, mesh=None, chunk=None):
            return StubPlan(self.n_chunks, self.depth)

    def reference_state(n_chunks: int):
        """The sequential-schedule film: chunks 0..n-1 accumulated in
        cursor order — the bit-identity baseline PROTO-FILM compares
        every explored interleaving's terminal film against."""
        plan = StubPlan(n_chunks, 1)
        state = plan.film.init_state()
        for c in range(n_chunks):
            state, _ = plan.dispatch(state, c)
        return state

    _HARNESS = {
        "StubFilm": StubFilm,
        "StubScene": StubScene,
        "StubPlan": StubPlan,
        "StubIntegrator": StubIntegrator,
        "reference_state": reference_state,
    }
    return _HARNESS


# --------------------------------------------------------------------------
# Scenarios
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class JobSpec:
    """One job the model may submit."""

    name: str
    tenant: str = "default"
    priority: int = 0
    n_chunks: int = 3
    checkpoint_every: int = 0
    depth: int = 1
    #: scene-affinity routing key for fleet scenarios (defaults to the
    #: job name; two jobs sharing a scene MUST co-locate while their
    #: replica stays healthy — PROTO-ROUTE-AFFINITY)
    scene: str = ""


@dataclass(frozen=True)
class Scenario:
    """A bounded exploration universe: the jobs available to submit,
    the CHAOS fault plan, and which decision kinds the explorer may
    enumerate."""

    name: str
    jobs: Tuple[JobSpec, ...]
    fault: str = ""
    allow: Tuple[str, ...] = ("submit", "step", "advance")
    #: >1 selects the fleet model (a FleetRouter over N LocalReplicas
    #: under one VirtualClock) with the router decision kinds
    #: ("rstep", k) / ("kill", k) / ("drain", k) in the grid
    replicas: int = 1


def smoke_scenarios(n_fault_chunks: int = 2) -> List[Scenario]:
    """The CI exploration grid: two-tenant interleavings at pipeline
    depths 1-3 (arrival orders x retirement orders x preempt/resume
    timings), crossed with every fault plan in the CHAOS protocol
    fault space on a single-job scenario (fault placements x
    recovery-ladder arms)."""
    from tpu_pbrt.chaos import protocol_fault_space

    out: List[Scenario] = []
    for depth in (1, 2, 3):
        out.append(Scenario(
            name=f"duo-d{depth}",
            jobs=(
                JobSpec("a1", tenant="a", n_chunks=3,
                        checkpoint_every=2, depth=depth),
                JobSpec("b1", tenant="b", n_chunks=2,
                        checkpoint_every=2, depth=depth),
            ),
            allow=("submit", "step", "advance", "preempt", "resume"),
        ))
    for i, fault in enumerate(protocol_fault_space(n_fault_chunks)):
        out.append(Scenario(
            name=f"fault-{i}:{fault or 'clean'}",
            jobs=(JobSpec("f1", n_chunks=3, checkpoint_every=2, depth=2),),
            fault=fault,
            allow=("submit", "step", "advance"),
        ))
    # the ISSUE-20 router grid: route / re-route / resume-elsewhere /
    # double-delivery, explored over 2 replicas under one VirtualClock
    out.append(Scenario(
        name="fleet-affine",
        jobs=(
            JobSpec("fa1", scene="sS", n_chunks=2, checkpoint_every=1),
            JobSpec("fa2", scene="sS", n_chunks=2, checkpoint_every=1),
        ),
        allow=("submit", "rstep", "advance"),
        replicas=2,
    ))
    out.append(Scenario(
        name="fleet-kill",
        jobs=(JobSpec("fk", scene="sK", n_chunks=3, checkpoint_every=1),),
        allow=("submit", "rstep", "advance", "kill"),
        replicas=2,
    ))
    out.append(Scenario(
        name="fleet-drain",
        jobs=(
            JobSpec("fd1", scene="sD", n_chunks=2, checkpoint_every=1),
            JobSpec("fd2", scene="sE", n_chunks=2, checkpoint_every=1),
        ),
        allow=("submit", "rstep", "advance", "drain"),
        replicas=2,
    ))
    return out


# --------------------------------------------------------------------------
# The protocol model
# --------------------------------------------------------------------------


class ProtocolModel:
    """The REAL RenderService under a VirtualClock, driven by explicit
    decisions, with the PROTO-* invariants checked after every one.

    Decisions (tuples):

    - ``("submit", i)`` — submit scenario job ``i``
    - ``("step",)``     — one scheduler step (dispatch / wait / idle)
    - ``("advance",)``  — move virtual time to just BEFORE the earliest
      open backoff deadline (epsilon/2 short: the adversarial placement
      that distinguishes one clock sample from two)
    - ``("preempt", name)`` / ``("resume", name)`` / ``("cancel", name)``

    Every decision appends one path-free line to ``self.log`` — the
    schedule-determinism artifact (same decision sequence => byte-
    identical log) — and any invariant breach appends
    ``(invariant, detail)`` to ``self.violations``.
    """

    EPS = 1e-6

    def __init__(self, scenario: Scenario, seed: int = 0):
        import tempfile

        from tpu_pbrt.chaos import CHAOS
        from tpu_pbrt.obs.flight import FLIGHT
        from tpu_pbrt.obs.trace import TRACE
        from tpu_pbrt.parallel import checkpoint as ckpt
        from tpu_pbrt.serve.service import RenderService
        from tpu_pbrt.utils.clock import VirtualClock

        self.scenario = scenario
        self.seed = int(seed)
        self.clock = VirtualClock(start=0.0, tick=self.EPS)
        self.tmpdir = tempfile.mkdtemp(prefix="protocheck_")
        self.svc = RenderService(
            seed=self.seed, spool_dir=self.tmpdir, clock=self.clock,
        )
        CHAOS.install(scenario.fault, self.seed)
        self._ckpt = ckpt
        self._watermark: Dict[str, int] = {}
        self.ckpt_writes = 0
        self.violations: List[Tuple[str, str]] = []
        self.log: List[str] = []
        self._unsubmitted = set(range(len(scenario.jobs)))
        self._done_checked: set = set()
        # PROTO-HBM (ISSUE 18): the layer-7 memory model evaluated on
        # the live service — peak watermark + cached static worst case
        self.hbm_peak = 0
        self._hbm_worst: Optional[int] = None
        self._obs = self._on_ckpt_write
        ckpt.register_write_observer(self._obs)
        # satellite: the recorders run on the SAME virtual timeline, so
        # flight heartbeats / trace spans emitted during exploration
        # carry monotone virtual timestamps (restored exactly in close)
        self._flight_prev = (FLIGHT._clock, FLIGHT._t0)
        FLIGHT.set_clock(self.clock)
        self._trace_prev = (TRACE._clock, TRACE._t0)
        TRACE.set_clock(self.clock)
        self.closed = False

    # -- observer ----------------------------------------------------------
    def _on_ckpt_write(self, path: str, cursor: int, rays: int) -> None:
        """Deferred-write linearity (PROTO-DEFER): the durable cursor at
        one path must be monotone — a clean publish below the watermark
        means a superseded deferred write replayed after a park or
        terminal supersession."""
        self.ckpt_writes += 1
        prev = self._watermark.get(path)
        if prev is not None and cursor < prev:
            self.violations.append((
                "PROTO-DEFER",
                f"superseded deferred write replayed: durable cursor "
                f"regressed {prev} -> {cursor} at the same checkpoint "
                f"path (write #{self.ckpt_writes})",
            ))
        self._watermark[path] = max(prev or 0, int(cursor))

    # -- decisions ---------------------------------------------------------
    def enabled_decisions(self) -> List[tuple]:
        """The legal decisions at the current state, in a deterministic
        order (the explorer's branching set)."""
        from tpu_pbrt.serve.service import PAUSED, _RUNNABLE, _TERMINAL

        allow = self.scenario.allow
        out: List[tuple] = []
        if "submit" in allow:
            out.extend(("submit", i) for i in sorted(self._unsubmitted))
        jobs = list(self.svc.jobs.values())
        live = [j for j in jobs if j.status not in _TERMINAL]
        if "step" in allow and any(j.status != PAUSED for j in live):
            out.append(("step",))
        if "advance" in allow:
            now = self.clock.peek()
            if any(
                j.status in _RUNNABLE and j.not_before > now for j in jobs
            ):
                out.append(("advance",))
        if "preempt" in allow:
            out.extend(
                ("preempt", j.job_id) for j in jobs
                if j.status in _RUNNABLE
            )
        if "resume" in allow:
            out.extend(
                ("resume", j.job_id) for j in jobs if j.status == PAUSED
            )
        if "cancel" in allow:
            out.extend(
                ("cancel", j.job_id) for j in jobs
                if j.status not in _TERMINAL
            )
        return out

    def apply(self, decision: tuple) -> str:
        """Apply one decision to the real service, then check every
        invariant and append the log line. Returns the outcome token."""
        from tpu_pbrt.serve.service import _RUNNABLE

        kind = decision[0]
        pre_nb = {j.job_id: j.not_before for j in self.svc.jobs.values()}
        pre_sched = len(self.svc.schedule)
        outcome = ""
        try:
            if kind == "submit":
                i = int(decision[1])
                spec = self.scenario.jobs[i]
                self._unsubmitted.discard(i)
                h = _harness()
                self.svc.submit(
                    compiled=(h["StubScene"](),
                              h["StubIntegrator"](spec.n_chunks, spec.depth)),
                    resident_key=f"stub:{spec.name}",
                    job_id=spec.name, tenant=spec.tenant,
                    priority=spec.priority,
                    checkpoint_every=spec.checkpoint_every,
                )
                outcome = f"submitted:{spec.name}"
            elif kind == "step":
                rid = self.svc.step()
                outcome = rid if rid is not None else "idle"
            elif kind == "advance":
                now = self.clock.peek()
                deadlines = [
                    j.not_before for j in self.svc.jobs.values()
                    if j.status in _RUNNABLE and j.not_before > now
                ]
                if deadlines:
                    target = min(deadlines) - self.EPS / 2
                    self.clock.advance_to(target)
                    outcome = f"advanced:{target:.6f}"
                else:
                    outcome = "noop"
            elif kind == "preempt":
                self.svc.preempt(decision[1])
                outcome = f"paused:{decision[1]}"
            elif kind == "resume":
                self.svc.resume(decision[1])
                outcome = f"resumed:{decision[1]}"
            elif kind == "cancel":
                self.svc.cancel(decision[1])
                outcome = f"cancelled:{decision[1]}"
            else:
                raise ValueError(f"unknown decision kind {kind!r}")
        except Exception as e:  # noqa: BLE001 — a crash IS a finding
            detail = str(e).replace(self.tmpdir, "<spool>")
            self.violations.append((
                "PROTO-CRASH",
                f"decision {decision} raised {type(e).__name__}: {detail}",
            ))
            outcome = f"crash:{type(e).__name__}"
        self._check_invariants(decision, kind, outcome, pre_nb, pre_sched)
        self._log_line(decision, outcome)
        return outcome

    def run(self, decisions) -> "ProtocolModel":
        for d in decisions:
            self.apply(tuple(d))
        return self

    # -- invariants ---------------------------------------------------------
    def _check_invariants(
        self, decision: tuple, kind: str, outcome: str,
        pre_nb: Dict[str, float], pre_sched: int,
    ) -> None:
        import numpy as np

        from tpu_pbrt.serve.service import DONE, _RUNNABLE, _TERMINAL

        svc = self.svc
        # PROTO-WEDGE: step answered idle while schedulable work exists
        # (the exact gap obs/health.py's watchdog flags as a wedge)
        if kind == "step" and outcome == "idle":
            stuck = svc._runnable(float("inf"))
            if stuck:
                gap = svc.health_steps - svc.last_progress_step
                self.violations.append((
                    "PROTO-WEDGE",
                    f"step() returned None with runnable work pending "
                    f"({[j.job_id for j in stuck]}); health watchdog gap "
                    f"{gap} step(s) with no cursor progress",
                ))
        # PROTO-VTIME: no banked credit at the submit boundary — the
        # submitter's tenant must sit at/above the busy tenants' floor
        if kind == "submit" and not outcome.startswith("crash"):
            spec = self.scenario.jobs[int(decision[1])]
            sch = svc.scheduler
            ts = sch._tenants.get(spec.tenant)
            floors = [
                sch._tenants[t].vtime
                for t in {
                    j.tenant for j in svc.jobs.values()
                    if j.status in _RUNNABLE and j.tenant != spec.tenant
                }
                if t in sch._tenants
            ]
            if floors:
                floor = min(floors)
                have = ts.vtime if ts is not None else None
                if have is None or have < floor - 1e-9:
                    self.violations.append((
                        "PROTO-VTIME",
                        f"tenant {spec.tenant!r} re-entered below the busy "
                        f"floor: vtime {have} < {floor:.6f} (banked "
                        f"credit — the PR-6 WFQ regression shape)",
                    ))
        # PROTO-PIN: residency pins balance the non-terminal holders
        pins = svc.residency.pin_counts()
        expected: Dict[str, int] = {}
        for j in svc.jobs.values():
            if j.status not in _TERMINAL:
                expected[j.resident_key] = expected.get(j.resident_key, 0) + 1
        for key in sorted(set(pins) | set(expected)):
            if pins.get(key, 0) != expected.get(key, 0):
                self.violations.append((
                    "PROTO-PIN",
                    f"residency pin imbalance for {key!r}: {pins.get(key, 0)}"
                    f" pin(s) vs {expected.get(key, 0)} live holder(s)",
                ))
        # PROTO-BACKOFF: deadlines are monotone per job, and nothing
        # dispatches from inside its pre-decision backoff window
        now = self.clock.peek()
        for j in svc.jobs.values():
            prev = pre_nb.get(j.job_id)
            if prev is not None and j.not_before < prev - 1e-12:
                self.violations.append((
                    "PROTO-BACKOFF",
                    f"job {j.job_id} backoff deadline moved backward: "
                    f"{prev:.6f} -> {j.not_before:.6f}",
                ))
        for job_id, _chunk in svc.schedule[pre_sched:]:
            nb = pre_nb.get(job_id, 0.0)
            if nb > now + 1e-9:
                self.violations.append((
                    "PROTO-BACKOFF",
                    f"job {job_id} dispatched at {now:.6f}, inside its "
                    f"backoff window (not_before {nb:.6f})",
                ))
        # PROTO-COUNT / PROTO-FILM at each terminal DONE
        for j in svc.jobs.values():
            if j.status != DONE or j.job_id in self._done_checked:
                continue
            self._done_checked.add(j.job_id)
            spec = next(
                s for s in self.scenario.jobs if s.name == j.job_id
            )
            res = j.result
            want = spec.n_chunks * RAYS_PER_CHUNK
            if res is None or int(res.rays_traced) != want:
                got = None if res is None else int(res.rays_traced)
                self.violations.append((
                    "PROTO-COUNT",
                    f"job {j.job_id} finished with rays_traced={got}, "
                    f"expected {want} ({spec.n_chunks} x {RAYS_PER_CHUNK}"
                    f") — lost or double-counted across the recovery "
                    f"ladder",
                ))
                continue
            ref = _harness()["reference_state"](spec.n_chunks)
            fs = res.film_state
            if not (
                np.array_equal(np.asarray(fs.rgb), np.asarray(ref.rgb))
                and np.array_equal(
                    np.asarray(fs.weight), np.asarray(ref.weight)
                )
            ):
                self.violations.append((
                    "PROTO-FILM",
                    f"job {j.job_id} terminal film differs bitwise from "
                    f"the sequential schedule's (interleaving or rollback "
                    f"changed the accumulation)",
                ))
        # PROTO-HBM (ISSUE 18): hbmcheck's static memory model,
        # cross-checked dynamically — the modeled watermark must stay
        # under the scenario's static worst case, parked/terminal jobs
        # must hold no device buffers, and the watermark must return to
        # baseline (resident scenes only) once the scenario drains
        from tpu_pbrt.serve.service import CANCELLED, FAILED, PARKED, PAUSED

        held, total = self._modeled_hbm()
        self.hbm_peak = max(self.hbm_peak, total)
        worst = self._static_worst_hbm()
        if total > worst:
            self.violations.append((
                "PROTO-HBM",
                f"modeled HBM watermark {total} B exceeds the static "
                f"worst case {worst} B after {decision!r} — the serve "
                f"stack holds more device memory than layer 6's model "
                f"admits",
            ))
        for j in svc.jobs.values():
            if (
                j.status in (PARKED, PAUSED, CANCELLED, FAILED)
                and j.state is not None
            ):
                self.violations.append((
                    "PROTO-HBM",
                    f"job {j.job_id} ({j.status}) retains its film carry "
                    f"— the park/terminal path must release HBM after "
                    f"the durable write lands",
                ))
            if j.status in _TERMINAL:
                n_ctr = (
                    len(j.ray_counts) + len(j.occ_counts)
                    + len(j.ctr_counts) + len(j.nf_counts)
                )
                if n_ctr or j.window is not None:
                    w = "live" if j.window is not None else "none"
                    self.violations.append((
                        "PROTO-HBM",
                        f"terminal job {j.job_id} ({j.status}) retains "
                        f"{n_ctr} per-slice counter buffer(s), window="
                        f"{w} — terminal paths must drop every device "
                        f"reference",
                    ))
        if (
            svc.jobs and not self._unsubmitted
            and all(j.status in _TERMINAL for j in svc.jobs.values())
            and held != 0
        ):
            self.violations.append((
                "PROTO-HBM",
                f"drained: every job terminal but the modeled job-held "
                f"HBM is {held} B, not 0 — the watermark did not return "
                f"to baseline (resident scenes only)",
            ))

    def _modeled_hbm(self) -> Tuple[int, int]:
        """(job-held bytes, total bytes) of the layer-7 memory model
        evaluated on the LIVE service: film carries (job.state), the
        un-donated in-flight window slices, and the per-slice counter
        scalars, plus resident scene bytes for the total. Terminal
        results (RenderResult.film_state) are intentional retention and
        excluded — the drain baseline is resident scenes only."""
        from tpu_pbrt.analysis.hbmcheck import film_state_bytes

        held = 0
        for j in self.svc.jobs.values():
            fb = 0
            if j.plan is not None:
                rx, ry = j.plan.film.full_resolution
                fb = film_state_bytes(rx, ry)
            if j.state is not None:
                held += fb
            if (
                j.window is not None
                and getattr(j.plan, "pipeline_depth", 1) > 1
            ):
                held += len(j.window) * fb
            held += 8 * (
                len(j.ray_counts) + len(j.occ_counts)
                + len(j.ctr_counts) + len(j.nf_counts)
            )
        return held, held + self.svc.residency.total_bytes()

    def _static_worst_hbm(self) -> int:
        """hbmcheck's static worst case specialized to this scenario —
        the bound PROTO-HBM holds the dynamic watermark to: per job,
        one stub resident scene + the live film carries of its depth +
        a full complement of per-slice counters."""
        if self._hbm_worst is None:
            from tpu_pbrt.analysis.hbmcheck import (
                COUNTER_BYTES_PER_SLICE, film_state_bytes,
            )
            from tpu_pbrt.integrators.common import live_film_carries

            fb = film_state_bytes(2, 2)  # the stub harness film
            total = 0
            for spec in self.scenario.jobs:
                total += fb  # scene_hbm_bytes of a StubScene (dev={})
                total += live_film_carries(spec.depth) * fb
                total += spec.n_chunks * COUNTER_BYTES_PER_SLICE
            self._hbm_worst = total
        return self._hbm_worst

    # -- artifacts ----------------------------------------------------------
    def _log_line(self, decision: tuple, outcome: str) -> None:
        svc = self.svc
        jobs = " ".join(
            f"{j.job_id}:{j.status}:c{j.cursor}:a{j.attempt}"
            f":nb{j.not_before:.6f}"
            for j in sorted(svc.jobs.values(), key=lambda j: j.job_id)
        )
        vt = ",".join(
            f"{t}={ts.vtime:.6f}"
            for t, ts in sorted(svc.scheduler._tenants.items())
        )
        self.log.append(
            f"{len(self.log):03d} {decision!r} -> {outcome} "
            f"@{self.clock.peek():.6f} | {jobs} | vt[{vt}] | "
            f"sched={len(svc.schedule)} ckpt={self.ckpt_writes}"
        )

    def fingerprint(self) -> tuple:
        """Abstract-state key for the explorer's visited-set pruning:
        everything scheduling-relevant, with deadlines made RELATIVE to
        the virtual clock (two states differing only by a time
        translation behave identically)."""
        now = self.clock.peek()
        jobs = tuple(
            (
                j.job_id, j.status, j.cursor, j.attempt, j.state is None,
                round(max(j.not_before - now, 0.0), 9),
                (len(j.window) if j.window is not None else -1),
                (tuple(c for c, _ in j.window.deferred)
                 if j.window is not None else ()),
                self._ckpt.checkpoint_exists(j.checkpoint_path),
            )
            for j in sorted(
                self.svc.jobs.values(), key=lambda j: j.job_id
            )
        )
        vt = tuple(
            (t, round(ts.vtime, 9))
            for t, ts in sorted(self.svc.scheduler._tenants.items())
        )
        return (jobs, vt, tuple(sorted(self._unsubmitted)))

    def close(self) -> None:
        """Restore every process-global the model armed (CHAOS plan,
        checkpoint write observer, recorder clocks) and drop the spool.
        Idempotent."""
        if self.closed:
            return
        self.closed = True
        import shutil

        from tpu_pbrt.chaos import CHAOS
        from tpu_pbrt.obs.flight import FLIGHT
        from tpu_pbrt.obs.trace import TRACE

        CHAOS.clear()
        self._ckpt.unregister_write_observer(self._obs)
        FLIGHT._clock, FLIGHT._t0 = self._flight_prev
        TRACE._clock, TRACE._t0 = self._trace_prev
        shutil.rmtree(self.tmpdir, ignore_errors=True)

    def __enter__(self) -> "ProtocolModel":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# --------------------------------------------------------------------------
# The fleet model (ISSUE 20): the router/replica handoff protocol
# --------------------------------------------------------------------------


class FleetModel:
    """N real RenderServices behind a real FleetRouter, one shared
    VirtualClock, driven by explicit decisions — the handoff protocol
    (route / re-route / resume-elsewhere / double-delivery) as a pure
    function of the decision sequence, with the PROTO-ROUTE-*
    invariants checked after every one.

    Decisions (tuples; same explorer contract as ProtocolModel):

    - ``("submit", i)``  — submit scenario job ``i`` THROUGH the router
    - ``("rstep", k)``   — one scheduler step on replica ``k``
    - ``("advance",)``   — virtual time to just before the earliest
      open backoff deadline across all alive replicas
    - ``("kill", k)``    — abrupt replica death + spool failover
    - ``("drain", k)``   — graceful drain + spool failover

    Invariants:

    - PROTO-ROUTE-AFFINITY — a submit of a seen scene key routes to
      the same replica while that replica stays healthy
    - PROTO-ROUTE-DUP — no job id has two live instances on alive
      replicas, and no job is DONE on more than one replica (the
      double-render guard the failover-skips-spool-consume mutant
      seeds a regression for)
    - PROTO-ROUTE-LOST — every admitted non-terminal job has exactly
      one live instance somewhere alive; every DONE record a DONE
      instance
    - PROTO-ROUTE-PIN — residency pins balance live holders on every
      alive replica (ProtocolModel's PROTO-PIN, per replica)
    - PROTO-ROUTE-FILM — every DONE film is bit-identical to the
      sequential single-replica schedule's, rays exactly
      ``n_chunks x RAYS_PER_CHUNK`` (failover resumes from the durable
      cursor, never re-accumulates)

    PROTO-DEFER rides along via the checkpoint write observer: the
    durable cursor at one router-owned spool path must stay monotone
    ACROSS replicas — a failover that re-renders retired chunks would
    regress it.
    """

    EPS = 1e-6

    def __init__(self, scenario: Scenario, seed: int = 0):
        import tempfile

        from tpu_pbrt.chaos import CHAOS
        from tpu_pbrt.fleet.router import FleetRouter, LocalReplica
        from tpu_pbrt.obs.flight import FLIGHT
        from tpu_pbrt.obs.trace import TRACE
        from tpu_pbrt.parallel import checkpoint as ckpt
        from tpu_pbrt.utils.clock import VirtualClock

        self.scenario = scenario
        self.seed = int(seed)
        self.clock = VirtualClock(start=0.0, tick=self.EPS)
        self.tmpdir = tempfile.mkdtemp(prefix="protocheck_fleet_")
        self._rids = [f"r{k}" for k in range(int(scenario.replicas))]
        replicas = [
            LocalReplica(
                rid, clock=self.clock, seed=self.seed,
                spool_dir=os.path.join(self.tmpdir, rid),
            )
            for rid in self._rids
        ]
        self.router = FleetRouter(
            replicas, clock=self.clock,
            spool_dir=os.path.join(self.tmpdir, "fleet"),
        )
        CHAOS.install(scenario.fault, self.seed)
        self._ckpt = ckpt
        self._watermark: Dict[str, int] = {}
        self.ckpt_writes = 0
        self.violations: List[Tuple[str, str]] = []
        self.log: List[str] = []
        self._unsubmitted = set(range(len(scenario.jobs)))
        self._done_checked: set = set()
        #: the model's own affinity expectation: scene key -> the
        #: replica the router last placed it on
        self._affinity: Dict[str, str] = {}
        self._obs = self._on_ckpt_write
        ckpt.register_write_observer(self._obs)
        self._flight_prev = (FLIGHT._clock, FLIGHT._t0)
        FLIGHT.set_clock(self.clock)
        self._trace_prev = (TRACE._clock, TRACE._t0)
        TRACE.set_clock(self.clock)
        self.closed = False

    def _on_ckpt_write(self, path: str, cursor: int, rays: int) -> None:
        """PROTO-DEFER across the fleet: one durable path, one monotone
        cursor — no matter WHICH replica writes it."""
        self.ckpt_writes += 1
        prev = self._watermark.get(path)
        if prev is not None and cursor < prev:
            self.violations.append((
                "PROTO-DEFER",
                f"durable cursor regressed {prev} -> {cursor} at one "
                f"spool path across the fleet (write #{self.ckpt_writes})"
                f" — a failover re-rendered already-durable chunks",
            ))
        self._watermark[path] = max(prev or 0, int(cursor))

    # -- decisions ---------------------------------------------------------
    def _key(self, spec: JobSpec) -> str:
        return f"stub:{spec.scene or spec.name}"

    def enabled_decisions(self) -> List[tuple]:
        from tpu_pbrt.serve.service import PAUSED, _RUNNABLE, _TERMINAL

        allow = self.scenario.allow
        healthy = self.router.healthy()
        out: List[tuple] = []
        if "submit" in allow and healthy:
            out.extend(("submit", i) for i in sorted(self._unsubmitted))
        now = self.clock.peek()
        any_backoff = False
        for k, rid in enumerate(self._rids):
            r = self.router.replicas[rid]
            if not r.alive:
                continue
            jobs = list(r.service.jobs.values())
            live = [j for j in jobs if j.status not in _TERMINAL]
            if "rstep" in allow and any(j.status != PAUSED for j in live):
                out.append(("rstep", k))
            any_backoff = any_backoff or any(
                j.status in _RUNNABLE and j.not_before > now for j in jobs
            )
        if "advance" in allow and any_backoff:
            out.append(("advance",))
        # eviction decisions keep at least one healthy survivor — a
        # fleet with nowhere left to route is outside the protocol
        for k, rid in enumerate(self._rids):
            r = self.router.replicas[rid]
            survivors = [h for h in healthy if h != rid]
            if "kill" in allow and r.alive and survivors:
                out.append(("kill", k))
            if "drain" in allow and r.alive and not r.draining and survivors:
                out.append(("drain", k))
        return out

    def apply(self, decision: tuple) -> str:
        from tpu_pbrt.serve.service import _RUNNABLE

        kind = decision[0]
        outcome = ""
        try:
            if kind == "submit":
                i = int(decision[1])
                spec = self.scenario.jobs[i]
                self._unsubmitted.discard(i)
                h = _harness()
                key = self._key(spec)
                expected = self._affinity.get(key)
                healthy_before = set(self.router.healthy())
                self.router.submit(
                    compiled=(h["StubScene"](),
                              h["StubIntegrator"](spec.n_chunks, spec.depth)),
                    resident_key=key, job_id=spec.name,
                    tenant=spec.tenant, priority=spec.priority,
                    checkpoint_every=spec.checkpoint_every,
                )
                rid = self.router.jobs[spec.name].rid
                if (
                    expected is not None
                    and expected in healthy_before
                    and rid != expected
                ):
                    self.violations.append((
                        "PROTO-ROUTE-AFFINITY",
                        f"scene key {key!r} routed to {rid}, but its "
                        f"compiled scene is resident on the still-"
                        f"healthy {expected} — the warm path lost",
                    ))
                self._affinity[key] = rid
                outcome = f"submitted:{spec.name}@{rid}"
            elif kind == "rstep":
                rid = self._rids[int(decision[1])]
                job = self.router.step_replica(rid)
                outcome = f"{rid}/{job}" if job is not None else f"{rid}/idle"
            elif kind == "advance":
                now = self.clock.peek()
                deadlines = [
                    j.not_before
                    for rid in self._rids
                    if self.router.replicas[rid].alive
                    for j in self.router.replicas[rid].service.jobs.values()
                    if j.status in _RUNNABLE and j.not_before > now
                ]
                if deadlines:
                    target = min(deadlines) - self.EPS / 2
                    self.clock.advance_to(target)
                    outcome = f"advanced:{target:.6f}"
                else:
                    outcome = "noop"
            elif kind in ("kill", "drain"):
                rid = self._rids[int(decision[1])]
                if kind == "kill":
                    moved = self.router.kill_replica(rid)
                else:
                    moved = self.router.drain_replica(rid)
                for job_id in moved:
                    rec = self.router.jobs[job_id]
                    self._affinity[rec.key] = rec.rid
                outcome = f"{kind}ed:{rid}+moved:{','.join(moved) or '-'}"
            else:
                raise ValueError(f"unknown decision kind {kind!r}")
        except Exception as e:  # noqa: BLE001 — a crash IS a finding
            detail = str(e).replace(self.tmpdir, "<spool>")
            self.violations.append((
                "PROTO-CRASH",
                f"decision {decision} raised {type(e).__name__}: {detail}",
            ))
            outcome = f"crash:{type(e).__name__}"
        self._check_invariants(decision)
        self._log_line(decision, outcome)
        return outcome

    def run(self, decisions) -> "FleetModel":
        for d in decisions:
            self.apply(tuple(d))
        return self

    # -- invariants ---------------------------------------------------------
    def _check_invariants(self, decision: tuple) -> None:
        import numpy as np

        from tpu_pbrt.serve.service import DONE, _TERMINAL

        router = self.router
        # instance census per admitted job: DUP / LOST
        for job_id, rec in router.jobs.items():
            live_on: List[str] = []
            done_on: List[str] = []
            for rid in self._rids:
                r = router.replicas[rid]
                j = r.service.jobs.get(job_id)
                if j is None:
                    continue
                if j.status == DONE:
                    done_on.append(rid)
                if r.alive and j.status not in _TERMINAL:
                    live_on.append(rid)
            if len(live_on) > 1:
                self.violations.append((
                    "PROTO-ROUTE-DUP",
                    f"job {job_id} is live on {live_on} simultaneously "
                    f"after {decision!r} — a failover delivered the job "
                    f"without consuming the previous instance",
                ))
            if len(done_on) > 1:
                self.violations.append((
                    "PROTO-ROUTE-DUP",
                    f"job {job_id} rendered to DONE on {done_on} — the "
                    f"same request paid for twice",
                ))
            if not rec.terminal and not live_on:
                self.violations.append((
                    "PROTO-ROUTE-LOST",
                    f"admitted job {job_id} has no live instance on any "
                    f"alive replica after {decision!r} — lost across a "
                    f"failover",
                ))
            if rec.terminal == DONE and not done_on:
                self.violations.append((
                    "PROTO-ROUTE-LOST",
                    f"job {job_id} recorded DONE at the router but no "
                    f"replica holds its result",
                ))
        # PROTO-ROUTE-PIN: ProtocolModel's pin balance, per alive replica
        for rid in self._rids:
            r = router.replicas[rid]
            if not r.alive:
                continue
            pins = r.service.residency.pin_counts()
            expected: Dict[str, int] = {}
            for j in r.service.jobs.values():
                if j.status not in _TERMINAL:
                    expected[j.resident_key] = (
                        expected.get(j.resident_key, 0) + 1
                    )
            for key in sorted(set(pins) | set(expected)):
                if pins.get(key, 0) != expected.get(key, 0):
                    self.violations.append((
                        "PROTO-ROUTE-PIN",
                        f"replica {rid} pin imbalance for {key!r}: "
                        f"{pins.get(key, 0)} pin(s) vs "
                        f"{expected.get(key, 0)} live holder(s)",
                    ))
        # PROTO-ROUTE-FILM at each fleet-terminal DONE
        for job_id, rec in router.jobs.items():
            if rec.terminal != DONE or job_id in self._done_checked:
                continue
            self._done_checked.add(job_id)
            spec = next(
                s for s in self.scenario.jobs if s.name == job_id
            )
            owner = router.replicas.get(rec.rid)
            j = None if owner is None else owner.service.jobs.get(job_id)
            res = None if j is None else j.result
            want = spec.n_chunks * RAYS_PER_CHUNK
            if res is None or int(res.rays_traced) != want:
                got = None if res is None else int(res.rays_traced)
                self.violations.append((
                    "PROTO-ROUTE-FILM",
                    f"job {job_id} finished with rays_traced={got}, "
                    f"expected {want} — chunks lost or re-accumulated "
                    f"across the failover resume",
                ))
                continue
            ref = _harness()["reference_state"](spec.n_chunks)
            fs = res.film_state
            if not (
                np.array_equal(np.asarray(fs.rgb), np.asarray(ref.rgb))
                and np.array_equal(
                    np.asarray(fs.weight), np.asarray(ref.weight)
                )
            ):
                self.violations.append((
                    "PROTO-ROUTE-FILM",
                    f"job {job_id} terminal film differs bitwise from "
                    f"the single-replica sequential schedule's — the "
                    f"re-route/resume changed the accumulation",
                ))

    # -- artifacts ----------------------------------------------------------
    def _log_line(self, decision: tuple, outcome: str) -> None:
        parts = []
        for rid in self._rids:
            r = self.router.replicas[rid]
            flag = ("" if r.alive else "!") + ("~" if r.draining else "")
            jobs = " ".join(
                f"{j.job_id}:{j.status}:c{j.cursor}:a{j.attempt}"
                f":nb{j.not_before:.6f}"
                for j in sorted(
                    r.service.jobs.values(), key=lambda j: j.job_id
                )
            )
            parts.append(f"{flag}{rid}[{jobs}]")
        self.log.append(
            f"{len(self.log):03d} {decision!r} -> {outcome} "
            f"@{self.clock.peek():.6f} | {' '.join(parts)} | "
            f"routes={len(self.router.routes)} "
            f"sheds={self.router.edge_sheds} ckpt={self.ckpt_writes}"
        )

    def fingerprint(self) -> tuple:
        now = self.clock.peek()
        reps = tuple(
            (
                rid, r.alive, r.draining,
                tuple(
                    (
                        j.job_id, j.status, j.cursor, j.attempt,
                        j.state is None,
                        round(max(j.not_before - now, 0.0), 9),
                    )
                    for j in sorted(
                        r.service.jobs.values(), key=lambda j: j.job_id
                    )
                ),
            )
            for rid in self._rids
            for r in (self.router.replicas[rid],)
        )
        recs = tuple(
            (
                job_id, rec.rid, rec.terminal, rec.failovers,
                self._ckpt.checkpoint_exists(rec.checkpoint_path),
            )
            for job_id, rec in sorted(self.router.jobs.items())
        )
        return (reps, recs, tuple(sorted(self._unsubmitted)))

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        import shutil

        from tpu_pbrt.chaos import CHAOS
        from tpu_pbrt.obs.flight import FLIGHT
        from tpu_pbrt.obs.trace import TRACE

        CHAOS.clear()
        self._ckpt.unregister_write_observer(self._obs)
        FLIGHT._clock, FLIGHT._t0 = self._flight_prev
        TRACE._clock, TRACE._t0 = self._trace_prev
        shutil.rmtree(self.tmpdir, ignore_errors=True)

    def __enter__(self) -> "FleetModel":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def make_model(scenario: Scenario, seed: int = 0):
    """The explorer's model factory: one scenario, one model — the
    fleet shape when the scenario asks for replicas, the single-service
    ProtocolModel otherwise (byte-identical to the pre-fleet grid)."""
    if int(getattr(scenario, "replicas", 1)) > 1:
        return FleetModel(scenario, seed=seed)
    return ProtocolModel(scenario, seed=seed)


# --------------------------------------------------------------------------
# Mutation-regression corpus
# --------------------------------------------------------------------------


@contextmanager
def _mut_clock_double_sample():
    """Reintroduce the PR-13 step() shape: the runnable filter samples
    the clock itself, and the backoff-wait computation samples AGAIN —
    a deadline between the two samples wedges the scheduler."""
    from tpu_pbrt.serve import service as S

    orig = S.RenderService.step

    def step(self):
        self.health_steps += 1
        job = self.scheduler.pick(self._runnable())  # hidden sample #1
        if job is None:
            now = self._now()  # sample #2 — the deadline race window
            waiting = [
                j.not_before for j in self.jobs.values()
                if j.status in S._RUNNABLE and j.not_before > now
            ]
            if not waiting:
                return None
            self.clock.sleep(max(min(waiting) - now, 0.0))
            job = self.scheduler.pick(self._runnable(self._now()))
            if job is None:
                return None
        return self._step_job(job)

    S.RenderService.step = step
    try:
        yield
    finally:
        S.RenderService.step = orig


@contextmanager
def _mut_wfq_banked_credit():
    """Remove reenter()'s busy clamp (the PR-6 fix): an idle tenant
    keeps its stale low vtime and re-enters with banked credit."""
    from tpu_pbrt.serve import queue as Q

    orig = Q.FairScheduler.reenter
    Q.FairScheduler.reenter = (
        lambda self, name, busy_tenants=(): None
    )
    try:
        yield
    finally:
        Q.FairScheduler.reenter = orig


@contextmanager
def _mut_defer_replay():
    """Replay the window's captured deferred writes AFTER the park's
    superseding durable write — the cursor-regression shape SV-DEFER's
    static aspect and PROTO-DEFER's dynamic watermark both target."""
    from tpu_pbrt.serve import service as S

    orig = S.RenderService._park

    def _park(self, job):
        stale = list(job.window.deferred) if job.window is not None else []
        orig(self, job)
        for _cursor, fn in stale:
            fn()

    S.RenderService._park = _park
    try:
        yield
    finally:
        S.RenderService._park = orig


@contextmanager
def _mut_park_leak():
    """Seeded ISSUE-18 leak: the park path writes the durable emergency
    checkpoint but SKIPS the film release — every preemption strands
    one film-state carry in HBM (the 'known suspect' hbmcheck's
    HC-LEAK static rule and PROTO-HBM's dynamic watermark both
    target)."""
    from tpu_pbrt.serve import service as S

    orig = S.RenderService._park

    def _park(self, job):
        carry = job.state
        orig(self, job)
        job.state = carry  # the release the mutant skips

    S.RenderService._park = _park
    try:
        yield
    finally:
        S.RenderService._park = orig


@contextmanager
def _mut_failover_skip_consume():
    """Seeded ISSUE-20 fleet bug: the failover path re-submits the job
    on the surviving replica WITHOUT consuming the old instance first
    (no cancel on the drained-but-alive source). Both replicas now
    consider the job theirs — the drained one holds it PAUSED with a
    durable spool entry, the survivor renders it again from that same
    spool: a double delivery, and a double render once the drain
    lifts. PROTO-ROUTE-DUP's live-instance census flags it at the
    drain decision."""
    from tpu_pbrt.fleet import router as R

    orig = R.FleetRouter._failover_job

    def _failover_job(self, job_id, from_rid, *, cancel_old=True):
        return orig(self, job_id, from_rid, cancel_old=False)

    R.FleetRouter._failover_job = _failover_job
    try:
        yield
    finally:
        R.FleetRouter._failover_job = orig


@dataclass(frozen=True)
class MutationCase:
    """One seeded historical bug: the mutation, the invariant expected
    to flag it, and the (hand-verified) decision sequence that
    deterministically reaches the violating state."""

    name: str
    historical: str
    expect: str
    scenario: Scenario
    decisions: Tuple[tuple, ...]


MUTATIONS = {
    "clock-double-sample": _mut_clock_double_sample,
    "wfq-banked-credit": _mut_wfq_banked_credit,
    "defer-replay-after-park": _mut_defer_replay,
    "park-skips-film-release": _mut_park_leak,
    "failover-skips-spool-consume": _mut_failover_skip_consume,
}

MUTATION_CASES: Tuple[MutationCase, ...] = (
    MutationCase(
        name="clock-double-sample",
        historical=(
            "PR-13 step(): runnable filter and backoff wait sampled the "
            "clock separately; a deadline between the samples wedged "
            "the scheduler"
        ),
        expect="PROTO-WEDGE",
        scenario=Scenario(
            name="mut-clock",
            jobs=(JobSpec("j", n_chunks=2, depth=1),),
            fault="dispatch:fail@chunk=0",
            allow=("submit", "step", "advance"),
        ),
        decisions=(("submit", 0), ("step",), ("advance",), ("step",)),
    ),
    MutationCase(
        name="wfq-banked-credit",
        historical=(
            "PR-6 FairScheduler: an idle tenant re-entered with its "
            "stale low vtime (banked credit) instead of the busy "
            "tenants' floor"
        ),
        expect="PROTO-VTIME",
        scenario=Scenario(
            name="mut-wfq",
            jobs=(
                JobSpec("a1", tenant="a", n_chunks=2),
                JobSpec("b1", tenant="b", n_chunks=3),
                JobSpec("a2", tenant="a", n_chunks=2),
            ),
            allow=("submit", "step", "advance"),
        ),
        decisions=(
            ("submit", 0), ("step",), ("step",),
            ("submit", 1), ("step",), ("step",),
            ("submit", 2),
        ),
    ),
    MutationCase(
        name="defer-replay-after-park",
        historical=(
            "pipelined cadence checkpoints: a deferred write captured "
            "before a park replayed after it, regressing the durable "
            "cursor below the park's superseding write"
        ),
        expect="PROTO-DEFER",
        scenario=Scenario(
            name="mut-defer",
            jobs=(JobSpec("j", n_chunks=6, checkpoint_every=2, depth=3),),
            allow=("submit", "step", "preempt"),
        ),
        decisions=(
            ("submit", 0), ("step",), ("step",), ("step",),
            ("preempt", "j"),
        ),
    ),
    MutationCase(
        name="park-skips-film-release",
        historical=(
            "serve park path: the preempted job's film carry stayed "
            "resident after the durable emergency checkpoint landed — "
            "every preemption leaked one film state (the ISSUE-18 "
            "HBM-liveness suspect hbmcheck gates)"
        ),
        expect="PROTO-HBM",
        scenario=Scenario(
            name="mut-hbm",
            jobs=(JobSpec("j", n_chunks=4, checkpoint_every=2, depth=2),),
            allow=("submit", "step", "preempt"),
        ),
        decisions=(
            ("submit", 0), ("step",), ("step",), ("preempt", "j"),
        ),
    ),
    MutationCase(
        name="failover-skips-spool-consume",
        historical=(
            "ISSUE-20 fleet failover: the drain path re-submitted a "
            "job on the surviving replica without consuming the old "
            "instance — both replicas rendered it (double delivery, "
            "double spend)"
        ),
        expect="PROTO-ROUTE-DUP",
        scenario=Scenario(
            name="mut-route",
            # key "stub:sJ" hashes to r0 on the 2-replica ring — the
            # drain target below is hand-verified like every corpus
            # decision sequence
            jobs=(JobSpec("j", scene="sJ", n_chunks=4,
                          checkpoint_every=2),),
            allow=("submit", "rstep", "advance", "drain"),
            replicas=2,
        ),
        decisions=(
            ("submit", 0), ("rstep", 0), ("rstep", 0), ("drain", 0),
        ),
    ),
)


def mutation_case(name: str) -> MutationCase:
    for case in MUTATION_CASES:
        if case.name == name:
            return case
    raise KeyError(
        f"unknown mutation {name!r} (have: "
        f"{[c.name for c in MUTATION_CASES]})"
    )


def run_mutation_case(
    name: str, seed: int = 0, mutate: bool = True,
) -> Tuple[List[Tuple[str, str]], List[str]]:
    """Run one corpus case's decision sequence against the real service
    — under its mutation (`mutate=True`, the regression check: the
    expected invariant MUST fire) or against the clean tree
    (`mutate=False`, the soundness check: NO invariant may fire).
    Returns (violations, event log)."""
    case = mutation_case(name)
    ctx = MUTATIONS[case.name]() if mutate else _null_ctx()
    with ctx:
        with make_model(case.scenario, seed=seed) as model:
            model.run(case.decisions)
            return list(model.violations), list(model.log)


@contextmanager
def _null_ctx():
    yield


# --------------------------------------------------------------------------
# Analysis-runner entry point
# --------------------------------------------------------------------------


def run_protocheck(
    seed: int = 0,
    root: Optional[str] = None,
    explore: bool = True,
    max_nodes: int = 40,
    max_depth: int = 7,
) -> Tuple[List[str], List[str]]:
    """Layer 5 as `python -m tpu_pbrt.analysis` runs it: the SV static
    lint over the tree, the mutation corpus (each seeded mutant must be
    caught, the clean tree must pass), and — when `explore` — a
    bounded explorer smoke over the CI scenario grid. Returns
    (errors, warnings)."""
    errors: List[str] = []
    warnings: List[str] = []
    if root is None:
        root = repo_root()
    for v in sv_lint_tree(root):
        errors.append(str(v))
    # the mutation corpus is the layer's self-test: a corpus that no
    # longer fires means the invariants rotted, not that the bugs died
    for case in MUTATION_CASES:
        viol, _log = run_mutation_case(case.name, seed=seed, mutate=True)
        if not any(inv == case.expect for inv, _ in viol):
            errors.append(
                f"mutation {case.name!r} not flagged: expected "
                f"{case.expect}, got {[inv for inv, _ in viol]}"
            )
        clean_viol, _log = run_mutation_case(
            case.name, seed=seed, mutate=False
        )
        if clean_viol:
            errors.append(
                f"clean tree violates invariants on corpus case "
                f"{case.name!r}: {clean_viol[:3]}"
            )
    if explore:
        explore_py = os.path.join(root, "tools", "explore.py")
        if not os.path.exists(explore_py):
            warnings.append(
                f"explorer not found at {explore_py}; bounded "
                "interleaving smoke skipped"
            )
        else:
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "tpu_pbrt_tools_explore", explore_py
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            errors.extend(mod.run_ci(
                seed=seed, max_nodes=max_nodes, max_depth=max_depth,
            ))
    return errors, warnings
