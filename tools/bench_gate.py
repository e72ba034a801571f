#!/usr/bin/env python
"""Live-capture perf regression gate (ISSUE 15): compare a FRESH bench
JSON line against the committed BENCH_r*.json trajectory and exit
non-zero, naming the regressed metric, when the new capture falls
outside per-metric tolerances.

    python bench.py ... | tail -1 > /tmp/fresh.json
    python tools/bench_gate.py /tmp/fresh.json

The committed baseline is the LATEST non-outage capture (bench_report's
outage rule: an explicit `infra_outage` flag, or value 0.0 with an
`error` — both mean the run measured the infrastructure, not the
renderer). A fresh capture that is itself an outage is EXEMPT (exit 0
with a loud note): the gate guards perf regressions, and failing CI
because the TPU pool was unreachable would train everyone to ignore it.

Per-metric tolerances (a metric is compared only when BOTH sides carry
it — early captures predate the telemetry block, and TPU_PBRT_METRICS=0
nulls the phase shares):

- Mray/s (`value`): fresh >= baseline * (1 - 10%)
- `mean_wave_occupancy`: fresh >= baseline - 0.05 (absolute)
- `telemetry.host_overlap_fraction`: fresh >= baseline - 0.10
- phase wall-time shares (from `telemetry.phase_seconds`): each
  phase's share of total within +-0.15 of the baseline's share

Higher-is-better only — a fresh capture that BEATS the baseline always
passes; commit it as the next BENCH_r* and the bar moves up.

`--selftest` proves all three behaviors with no fresh capture: the
baseline gates itself (pass), an outage row is exempt, and a synthetic
50% throughput regression fails naming the metric. With no capture
committed (the state since PR 21) it gates a synthetic baseline written
to a temporary directory. That is the tools/ci.sh scope-stage leg.
Standard library only.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (metric label, kind, tolerance) — kind "rel" floors at base*(1-tol),
#: "abs" floors at base-tol; both one-sided (higher is better)
TOLERANCES = (
    ("value", "rel", 0.10),
    ("mean_wave_occupancy", "abs", 0.05),
    ("telemetry.host_overlap_fraction", "abs", 0.10),
)
#: two-sided tolerance on each phase's share of total phase seconds
PHASE_SHARE_TOL = 0.15


def is_outage(line: Dict[str, Any]) -> bool:
    """bench_report.py's rule, shared verbatim: the explicit flag, or
    the pre-PR-4 shape (zero throughput + an error string)."""
    return bool(line.get("infra_outage")) or (
        line.get("value") == 0.0 and bool(line.get("error"))
    )


def load_capture(path: str) -> Dict[str, Any]:
    """A bench line: either bench.py's raw JSON line, or a committed
    BENCH_r* wrapper ({"n", "cmd", "rc", "parsed"}) whose `parsed` is
    the line."""
    with open(path) as f:
        doc = json.load(f)
    if "parsed" in doc and isinstance(doc.get("parsed"), dict):
        return doc["parsed"]
    return doc


def committed_baseline(
    pattern: Optional[str] = None,
) -> Tuple[Optional[str], Optional[Dict[str, Any]]]:
    """(run name, bench line) of the latest committed non-outage
    capture, or (None, None) when the trajectory has no usable row."""
    paths = sorted(glob.glob(pattern or os.path.join(REPO, "BENCH_r*.json")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and parsed and not is_outage(parsed):
            name = doc.get("n") or os.path.basename(path)
            return str(name), parsed
    return None, None


def _get(line: Dict[str, Any], dotted: str) -> Optional[float]:
    cur: Any = line
    for part in dotted.split("."):
        if not isinstance(cur, dict) or cur.get(part) is None:
            return None
        cur = cur[part]
    try:
        return float(cur)
    except (TypeError, ValueError):
        return None


def _phase_shares(line: Dict[str, Any]) -> Optional[Dict[str, float]]:
    phases = (line.get("telemetry") or {}).get("phase_seconds")
    if not isinstance(phases, dict) or not phases:
        return None
    secs = {
        ph: float(agg.get("seconds", 0.0))
        for ph, agg in phases.items()
        if isinstance(agg, dict)
    }
    total = sum(secs.values())
    if total <= 0:
        return None
    return {ph: s / total for ph, s in secs.items()}


def compare(
    baseline: Dict[str, Any], fresh: Dict[str, Any]
) -> Tuple[List[str], List[str]]:
    """(failures, compared-metric notes). Failures name the metric."""
    fails: List[str] = []
    notes: List[str] = []
    for metric, kind, tol in TOLERANCES:
        base, new = _get(baseline, metric), _get(fresh, metric)
        if base is None or new is None:
            continue
        floor = base * (1.0 - tol) if kind == "rel" else base - tol
        notes.append(
            f"{metric}: {new:g} vs baseline {base:g} (floor {floor:g})"
        )
        if new < floor:
            fails.append(
                f"{metric} regressed: {new:g} < floor {floor:g} "
                f"(baseline {base:g}, tolerance "
                f"{'-' + format(tol, '.0%') if kind == 'rel' else f'-{tol}'})"
            )
    b_sh, f_sh = _phase_shares(baseline), _phase_shares(fresh)
    if b_sh and f_sh:
        for ph in sorted(set(b_sh) & set(f_sh)):
            delta = f_sh[ph] - b_sh[ph]
            notes.append(
                f"phase_share[{ph}]: {f_sh[ph]:.3f} vs {b_sh[ph]:.3f}"
            )
            if abs(delta) > PHASE_SHARE_TOL:
                fails.append(
                    f"phase_share[{ph}] moved {delta:+.3f} "
                    f"(> +-{PHASE_SHARE_TOL}): the time-attribution "
                    "mix shifted, not just the throughput"
                )
    if not notes:
        fails.append(
            "no comparable metric between baseline and fresh capture "
            "(schema drift?)"
        )
    return fails, notes


def gate(fresh: Dict[str, Any], pattern: Optional[str] = None) -> int:
    if is_outage(fresh):
        print(
            "bench_gate: fresh capture is an INFRA OUTAGE "
            f"(error: {str(fresh.get('error'))[:120]!r}) — exempt, "
            "not a perf verdict"
        )
        return 0
    name, baseline = committed_baseline(pattern)
    if baseline is None:
        print("bench_gate: no committed non-outage baseline; nothing to gate")
        return 0
    fails, notes = compare(baseline, fresh)
    for n in notes:
        print(f"  {n}")
    if fails:
        for f in fails:
            print(f"FAIL bench_gate vs {name}: {f}", file=sys.stderr)
        return 1
    print(f"bench_gate OK vs {name} ({len(notes)} metric(s) compared)")
    return 0


def selftest() -> int:
    """Three behaviors, zero TPUs: self-pass, outage exemption, and a
    synthetic regression that must fail naming its metric."""
    fails: List[str] = []
    pattern = None
    name, baseline = committed_baseline()
    if baseline is None:
        tmp = tempfile.mkdtemp(prefix="bench_gate_selftest_")
        with open(os.path.join(tmp, "BENCH_r00.json"), "w") as f:
            json.dump({"n": "synthetic", "cmd": "selftest", "rc": 0,
                       "parsed": {"metric": "m", "value": 1.0,
                                  "unit": "Mray/s", "vs_baseline": 0.01,
                                  "mean_wave_occupancy": 0.9}}, f)
        pattern = os.path.join(tmp, "BENCH_r*.json")
        name, baseline = committed_baseline(pattern)

    if gate(dict(baseline), pattern) != 0:
        fails.append(f"baseline {name} does not pass its own gate")

    outage = {"value": 0.0, "error": "synthetic: backend unreachable"}
    if gate(outage) != 0:
        fails.append("outage capture was not exempted")

    slow = dict(baseline)
    slow["value"] = float(baseline.get("value", 0.0)) * 0.5
    c_fails, _ = compare(baseline, slow)
    if not any("value" in f for f in c_fails):
        fails.append("50% throughput regression not caught by name")

    for f in fails:
        print(f"FAIL bench_gate-selftest: {f}", file=sys.stderr)
    if not fails:
        print(f"bench_gate selftest OK (baseline: {name})")
    return 1 if fails else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python tools/bench_gate.py")
    ap.add_argument(
        "fresh", nargs="?",
        help="fresh bench JSON (bench.py line, or a BENCH_r* wrapper)",
    )
    ap.add_argument(
        "--baseline-glob", default="",
        help="override the committed-capture glob (default: repo "
             "BENCH_r*.json)",
    )
    ap.add_argument(
        "--selftest", action="store_true",
        help="self-pass + outage exemption + synthetic regression",
    )
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.fresh:
        ap.error("pass a fresh bench JSON file (or --selftest)")
    try:
        fresh = load_capture(args.fresh)
    except (OSError, ValueError) as e:
        print(f"FAIL bench_gate: unreadable capture: {e}", file=sys.stderr)
        return 1
    return gate(fresh, args.baseline_glob or None)


if __name__ == "__main__":
    sys.exit(main())
