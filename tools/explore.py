#!/usr/bin/env python
"""Bounded exhaustive explorer for the serve/dispatch protocol
(analysis layer 5 — the dynamic half of protocheck).

`tpu_pbrt/analysis/protocheck.py` makes a whole RenderService run a
pure deterministic function of an explicit decision sequence (the
VirtualClock seam + stub chunk dispatches). This tool enumerates those
sequences — job arrival orders x slice retirement orders at pipeline
depths 1-3 x fault placements from the CHAOS grammar x preempt/resume
timings — to a configurable depth, running the REAL service and
checking every PROTO-* invariant after every decision:

    python tools/explore.py --ci                      # CI smoke grid
    python tools/explore.py --nodes 200 --depth 10    # deeper search
    python tools/explore.py --mutate clock-double-sample
    python tools/explore.py --list-mutations
    python tools/explore.py --ci --trace-out /tmp/explore_trace.json

The search is a breadth-first walk over decision prefixes with
DPOR-style state pruning: each prefix is replayed on a fresh model
(cheap — stub dispatches are 2x2 numpy adds), and a prefix whose
abstract state fingerprint (job statuses/cursors/attempts, RELATIVE
backoff deadlines, window contents, tenant vtimes) was already visited
is not expanded — interleavings that merely permute into the same
protocol state are explored once.

Exit status: `--mutate` exits NON-ZERO when the seeded mutant's
expected invariant fires (the regression corpus asserts detection);
`--ci` and the default exploration exit non-zero when any violation or
determinism mismatch is found on the clean tree.

Determinism gate (PROTO-DET): every scenario's canonical full-drain
sequence is executed twice on fresh models; the event logs must be
byte-identical. `--trace-out` exports the canonical run's tpu-scope
trace (virtual-time stamps, `otherData.clock = "virtual"`) so
`tools/scope.py --check` can validate explorer timelines in CI.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, List, Optional, Tuple

# runnable as a plain script from anywhere (tools/ is not a package)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu_pbrt.analysis import protocheck as pc  # noqa: E402


# --------------------------------------------------------------------------
# Exploration
# --------------------------------------------------------------------------


class Explorer:
    """Bounded BFS over decision prefixes of one scenario."""

    def __init__(
        self, scenario: pc.Scenario, seed: int = 0,
        max_nodes: int = 40, max_depth: int = 7,
    ):
        self.scenario = scenario
        self.seed = int(seed)
        self.max_nodes = int(max_nodes)
        self.max_depth = int(max_depth)
        self.nodes = 0
        self.pruned = 0
        #: [(invariant, detail, decision prefix)]
        self.violations: List[Tuple[str, str, tuple]] = []

    def _replay(self, prefix: tuple) -> Tuple[list, tuple, List[str]]:
        """Fresh model, replay `prefix`. Returns (violations,
        fingerprint, enabled decisions)."""
        with pc.make_model(self.scenario, seed=self.seed) as model:
            model.run(prefix)
            return (
                list(model.violations),
                model.fingerprint(),
                model.enabled_decisions(),
            )

    def run(self) -> "Explorer":
        frontier: List[tuple] = [()]
        seen: set = set()
        while frontier and self.nodes < self.max_nodes:
            prefix = frontier.pop(0)
            self.nodes += 1
            viol, fp, enabled = self._replay(prefix)
            if viol:
                self.violations.extend(
                    (inv, detail, prefix) for inv, detail in viol
                )
                continue  # a violating state's successors add no news
            if fp in seen:
                self.pruned += 1
                continue
            seen.add(fp)
            if len(prefix) >= self.max_depth:
                continue
            frontier.extend(prefix + (d,) for d in enabled)
        return self


def canonical_drain(
    scenario: pc.Scenario, seed: int = 0, max_steps: int = 400,
) -> Tuple[tuple, List[str], List[Tuple[str, str]]]:
    """The canonical sequential schedule: submit every job in spec
    order, then step (waiting out backoff windows) until nothing is
    schedulable. Returns (decisions, event log, violations) — the
    determinism gate replays the decisions and compares the logs."""
    decisions: List[tuple] = []
    with pc.make_model(scenario, seed=seed) as model:
        for i in range(len(scenario.jobs)):
            d = ("submit", i)
            model.apply(d)
            decisions.append(d)
        for _ in range(max_steps):
            d = _drain_pick(model.enabled_decisions())
            if d is None:
                break
            model.apply(d)
            decisions.append(d)
            if model.violations:
                break
        return tuple(decisions), list(model.log), list(model.violations)


def _drain_pick(enabled: List[tuple]) -> Optional[tuple]:
    """The canonical drain's next decision: the first step — ("step",)
    single-service, ("rstep", k) in replica order for fleet scenarios —
    else wait out a backoff window. Kill/drain decisions are never
    canonical (they are explored, not drained through)."""
    d = next((x for x in enabled if x[0] in ("step", "rstep")), None)
    if d is None:
        d = next((x for x in enabled if x[0] == "advance"), None)
    return d


def replay_log(
    scenario: pc.Scenario, decisions: tuple, seed: int = 0,
) -> List[str]:
    with pc.make_model(scenario, seed=seed) as model:
        model.run(decisions)
        return list(model.log)


def export_trace(
    scenario: pc.Scenario, path: str, seed: int = 0,
) -> Optional[str]:
    """Run the canonical drain with the tpu-scope trace armed and
    export it to `path` — virtual-time stamps throughout, so
    tools/scope.py must accept a non-wall timeline."""
    from tpu_pbrt.obs.trace import TRACE

    prev_path = TRACE._path
    TRACE.configure(path)
    TRACE.reset()
    try:
        with pc.make_model(scenario, seed=seed) as model:
            for i in range(len(scenario.jobs)):
                model.apply(("submit", i))
            for _ in range(400):
                d = _drain_pick(model.enabled_decisions())
                if d is None:
                    break
                model.apply(d)
            # export INSIDE the model context: the clock is still the
            # VirtualClock, so otherData.clock stamps "virtual"
            return TRACE.export(path)
    finally:
        TRACE.configure(prev_path)
        TRACE.reset()


# --------------------------------------------------------------------------
# CI entry point (also called by run_protocheck via importlib)
# --------------------------------------------------------------------------


def run_ci(
    seed: int = 0, max_nodes: int = 40, max_depth: int = 7,
    verbose: bool = False,
) -> List[str]:
    """The bounded clean-tree smoke: explore every scenario in the CI
    grid under the node/depth budget, and gate schedule determinism on
    every canonical drain. Returns error strings (empty = clean)."""
    errors: List[str] = []
    for scenario in pc.smoke_scenarios():
        ex = Explorer(
            scenario, seed=seed, max_nodes=max_nodes, max_depth=max_depth,
        ).run()
        if verbose:
            print(
                f"  {scenario.name}: {ex.nodes} node(s), "
                f"{ex.pruned} pruned, {len(ex.violations)} violation(s)"
            )
        for inv, detail, prefix in ex.violations[:3]:
            errors.append(
                f"[{scenario.name}] {inv}: {detail} "
                f"(decisions: {list(prefix)})"
            )
        decisions, log1, viol = canonical_drain(scenario, seed=seed)
        for inv, detail in viol[:3]:
            errors.append(
                f"[{scenario.name}] canonical drain: {inv}: {detail}"
            )
        log2 = replay_log(scenario, decisions, seed=seed)
        if log1 != log2:
            diff = next(
                (i for i, (a, b) in enumerate(zip(log1, log2)) if a != b),
                min(len(log1), len(log2)),
            )
            errors.append(
                f"[{scenario.name}] PROTO-DET: replaying the same "
                f"decision sequence diverged at event {diff} "
                f"(len {len(log1)} vs {len(log2)})"
            )
    return errors


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="bounded interleaving & fault-schedule explorer for "
        "the serve/dispatch protocol (analysis layer 5)"
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--depth", type=int, default=7,
        help="max decisions per explored sequence",
    )
    ap.add_argument(
        "--nodes", type=int, default=40,
        help="max replayed prefixes per scenario",
    )
    ap.add_argument(
        "--ci", action="store_true",
        help="fixed-budget clean-tree smoke over the scenario grid",
    )
    ap.add_argument(
        "--mutate", metavar="NAME",
        help="run a seeded mutation-corpus case; exits non-zero when "
        "the expected invariant fires (detection asserted)",
    )
    ap.add_argument(
        "--list-mutations", action="store_true",
        help="list the mutation-regression corpus and exit",
    )
    ap.add_argument(
        "--trace-out", metavar="PATH",
        help="export the canonical duo-d2 drain's tpu-scope trace "
        "(virtual-time stamps) to PATH",
    )
    args = ap.parse_args(argv)

    if args.list_mutations:
        for case in pc.MUTATION_CASES:
            print(f"{case.name}: expects {case.expect} — {case.historical}")
        return 0

    if args.mutate:
        case = pc.mutation_case(args.mutate)
        viol, log = pc.run_mutation_case(
            case.name, seed=args.seed, mutate=True,
        )
        for line in log:
            print(f"  {line}")
        hit = [v for v in viol if v[0] == case.expect]
        for inv, detail in viol:
            print(f"PROTOCHECK VIOLATION {inv}: {detail}")
        if hit:
            print(
                f"mutation {case.name!r} detected by {case.expect} "
                f"(seeded regression: {case.historical})"
            )
            return 1
        print(
            f"mutation {case.name!r} NOT detected — expected "
            f"{case.expect}, got {[inv for inv, _ in viol]}"
        )
        return 0

    errors = run_ci(
        seed=args.seed, max_nodes=args.nodes, max_depth=args.depth,
        verbose=True,
    )
    if args.trace_out:
        duo = next(
            s for s in pc.smoke_scenarios() if s.name == "duo-d2"
        )
        out = export_trace(duo, args.trace_out, seed=args.seed)
        print(f"trace exported: {out}")
    for e in errors:
        print(f"PROTOCHECK {e}")
    print(
        f"protocheck explorer: {'CLEAN' if not errors else 'VIOLATIONS'} "
        f"({len(errors)} finding(s))"
    )
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
