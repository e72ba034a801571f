"""`python -m tpu_pbrt.analysis` — run the full analysis suite.

Stages (each skippable):
- layer 1, AST lint (`lint.py`) — always runs;
- layer 2, jaxpr/compile audit (`audit.py`) — `--no-audit` skips (it
  compiles small render programs, a few seconds on CPU);
- layer 3, jaxcost static roofline + budget gate (`cost.py`) —
  `--no-cost` skips; `--update-budgets` refreshes the committed
  `tpu_pbrt/analysis/budgets.json` instead of gating against it;
- layer 4, shardcheck replication analysis (`shardcheck.py`) —
  `--no-shardcheck` skips;
- layer 5, protocheck serve/dispatch protocol verification
  (`protocheck.py`) — the SV-* static rules over the protocol modules,
  the seeded mutation-regression corpus, and a bounded interleaving/
  fault-schedule exploration of the REAL service under a virtual clock
  (`tools/explore.py`); `--no-protocheck` skips;
- layer 6, hbmcheck static HBM residency/liveness/capacity
  verification of the serve stack (`hbmcheck.py`) — the HC-* rules:
  worst-case footprint vs the per-platform capacity table + the
  committed `hbm_budgets.json` (HC-CAP, refreshed by
  `--update-budgets`), terminal-path device-buffer release (HC-LEAK),
  residency-estimate accuracy (HC-ACCT), and donation-alias dedup
  (HC-ALIAS); `--no-hbmcheck` skips.

Exit code 0 iff no error-severity findings in any stage that ran. A
stage that crashes is reported as that stage's failure and the REST of
the stages still run — a multi-stage run always reports every failing
stage before exiting non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def _setup_jax_env() -> None:
    """One-time jax process setup shared by every jaxpr-tracing stage.
    Must happen before jax initializes a backend."""
    import os

    # only when the operator EXPLICITLY selected cpu (tools/ci.sh
    # does): unset JAX_PLATFORMS on a TPU VM means a TPU backend,
    # which must not inherit the unoptimized-CPU pipeline flag
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_backend_optimization_level" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_backend_optimization_level=0"
            ).strip()
    from tpu_pbrt.config import place_compile_cache

    place_compile_cache()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tpu_pbrt.analysis")
    ap.add_argument(
        "paths", nargs="*", help="files to lint (default: all of tpu_pbrt/)"
    )
    ap.add_argument(
        "--no-audit", action="store_true",
        help="skip the jaxpr/compile-time audit layer",
    )
    ap.add_argument(
        "--no-cost", action="store_true",
        help="skip the jaxcost roofline/budget stage",
    )
    ap.add_argument(
        "--no-shardcheck", action="store_true",
        help="skip the shard_map replication analysis",
    )
    ap.add_argument(
        "--no-protocheck", action="store_true",
        help="skip the serve/dispatch protocol verification layer",
    )
    ap.add_argument(
        "--no-hbmcheck", action="store_true",
        help="skip the static HBM residency/liveness/capacity layer",
    )
    ap.add_argument(
        "--update-budgets", action="store_true",
        help="refresh tpu_pbrt/analysis/budgets.json AND "
             "hbm_budgets.json from the current tree instead of "
             "gating against them (commit the result)",
    )
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    from tpu_pbrt.analysis.lint import PRAGMA_BUDGET, lint_tree

    repo_root = Path(__file__).resolve().parents[2]
    paths = [Path(p).resolve() for p in args.paths] or None
    violations, pragmas = lint_tree(repo_root, paths)
    over_budget = paths is None and pragmas > PRAGMA_BUDGET

    need_jax = not (
        args.no_audit and args.no_cost and args.no_shardcheck
        and args.no_protocheck and args.no_hbmcheck
    )
    if need_jax:
        # CPU audit/cost/shardcheck compile or trace tiny programs;
        # the unoptimized XLA pipeline + the repo compilation cache
        # keep this to seconds.
        _setup_jax_env()

    # every stage runs inside its own guard: a stage that CRASHES is
    # reported as that stage's failure and the remaining stages still
    # run, so one broken layer can't hide findings from the others
    def _stage(fn, sink):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001
            sink.append(f"stage crashed: {type(e).__name__}: {e}")
            return None

    audit_failures: list = []
    if not args.no_audit:
        def _audit():
            from tpu_pbrt.analysis.audit import run_audit

            return run_audit()

        audit_failures = _stage(_audit, audit_failures) or audit_failures

    cost_errors: list = []
    cost_warnings: list = []
    rollups = {}
    cost_findings: list = []
    if not args.no_cost:
        def _cost():
            from tpu_pbrt.analysis.cost import run_cost

            return run_cost(update=args.update_budgets)

        out = _stage(_cost, cost_errors)
        if out is not None:
            cost_errors, cost_warnings, rollups, cost_findings = out

    shard_errors: list = []
    shard_warnings: list = []
    if not args.no_shardcheck:
        def _shard():
            from tpu_pbrt.analysis.shardcheck import run_shardcheck

            return run_shardcheck()

        out = _stage(_shard, shard_errors)
        if out is not None:
            shard_errors, shard_warnings = out

    proto_errors: list = []
    proto_warnings: list = []
    if not args.no_protocheck:
        def _proto():
            from tpu_pbrt.analysis.protocheck import run_protocheck

            return run_protocheck(root=str(repo_root))

        out = _stage(_proto, proto_errors)
        if out is not None:
            proto_errors, proto_warnings = out

    hbm_errors: list = []
    hbm_warnings: list = []
    if not args.no_hbmcheck:
        def _hbm():
            from tpu_pbrt.analysis.hbmcheck import run_hbmcheck

            return run_hbmcheck(
                update=args.update_budgets, root=str(repo_root)
            )

        out = _stage(_hbm, hbm_errors)
        if out is not None:
            hbm_errors, hbm_warnings = out

    errors = [v for v in violations if v.severity == "error"]
    ok = not (
        errors or audit_failures or over_budget or cost_errors
        or shard_errors or proto_errors or hbm_errors
    )
    if args.format == "json":
        print(
            json.dumps(
                {
                    "lint": [v.__dict__ for v in violations],
                    "audit": audit_failures,
                    "cost": {
                        "rollups": {
                            k: r.to_json() for k, r in rollups.items()
                        },
                        "findings": [
                            {
                                "rule": f.rule, "entry": f.entry,
                                "detail": f.detail,
                                "severity": f.severity,
                                "waived": f.waived,
                            }
                            for f in cost_findings
                        ],
                        "errors": cost_errors,
                        "warnings": cost_warnings,
                    },
                    "shardcheck": {
                        "errors": shard_errors,
                        "warnings": shard_warnings,
                    },
                    "protocheck": {
                        "errors": proto_errors,
                        "warnings": proto_warnings,
                    },
                    "hbmcheck": {
                        "errors": hbm_errors,
                        "warnings": hbm_warnings,
                    },
                    "pragmas": pragmas,
                    "pragma_budget": PRAGMA_BUDGET,
                    "ok": ok,
                }
            )
        )
    else:
        for v in violations:
            print(v)
        for f in audit_failures:
            print(f"AUDIT: {f}")
        for w in cost_warnings:
            print(f"COST [warning]: {w}")
        for e in cost_errors:
            print(f"COST [error]: {e}")
        for w in shard_warnings:
            print(f"SHARDCHECK [warning]: {w}")
        for e in shard_errors:
            print(f"SHARDCHECK [error]: {e}")
        for w in proto_warnings:
            print(f"PROTOCHECK [warning]: {w}")
        for e in proto_errors:
            print(f"PROTOCHECK [error]: {e}")
        for w in hbm_warnings:
            print(f"HBMCHECK [warning]: {w}")
        for e in hbm_errors:
            print(f"HBMCHECK [error]: {e}")
        if args.update_budgets and not args.no_cost:
            from tpu_pbrt.analysis.cost import BUDGETS_PATH

            print(f"jaxcost: budgets refreshed -> {BUDGETS_PATH}")
        if args.update_budgets and not args.no_hbmcheck:
            from tpu_pbrt.analysis.hbmcheck import (
                BUDGETS_PATH as HBM_BUDGETS_PATH,
            )

            print(
                f"hbmcheck: HBM budgets refreshed -> {HBM_BUDGETS_PATH}"
            )
        n_warn = len(violations) - len(errors)
        # a SKIPPED stage must not read as a clean one in the summary
        audit_part = (
            "audit skipped" if args.no_audit
            else f"{len(audit_failures)} audit failure(s)"
        )
        cost_part = (
            "cost skipped" if args.no_cost
            else f"{len(cost_errors)} cost error(s)"
        )
        shard_part = (
            "shardcheck skipped" if args.no_shardcheck
            else f"{len(shard_errors)} shardcheck error(s)"
        )
        proto_part = (
            "protocheck skipped" if args.no_protocheck
            else f"{len(proto_errors)} protocheck error(s)"
        )
        hbm_part = (
            "hbmcheck skipped" if args.no_hbmcheck
            else f"{len(hbm_errors)} hbmcheck error(s)"
        )
        print(
            f"jaxlint: {len(errors)} error(s), {n_warn} warning(s), "
            f"{audit_part}, {cost_part}, {shard_part}, {proto_part}, "
            f"{hbm_part}, "
            f"{pragmas} pragma suppression(s) (budget {PRAGMA_BUDGET})"
        )
        if over_budget:
            print(
                f"jaxlint: pragma budget exceeded ({pragmas} > "
                f"{PRAGMA_BUDGET}) — fix the code instead of suppressing"
            )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
