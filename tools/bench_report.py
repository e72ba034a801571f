#!/usr/bin/env python
"""Aggregate the committed BENCH_r*.json capture files into ONE perf
trajectory table (ISSUE 10 satellite).

Each capture is a {"n", "cmd", "rc", "tail", "parsed"} wrapper around
bench.py's single JSON line; the trajectory — Mray/s, occupancy,
roofline ratio, outage diagnosis — would otherwise live in
separate files nobody can read at a glance. This tool renders them as a
markdown table (default) or JSON (--json), and it is a SCHEMA GATE: a
capture file that no longer matches the wrapper/bench-line schema exits
non-zero, so tools/ci.sh catches bench-JSON drift on every PR before a
real capture silently loses fields. No capture is committed at present
(PR 21 removed the ones taken on a machine that no longer exists; the
`benchmark` PR defines what replaces them): an empty set is not drift.

    python tools/bench_report.py                    # repo BENCH_r*.json
    python tools/bench_report.py BENCH_r0*.json --json
    python tools/bench_report.py --out BENCH_REPORT.md

Needs nothing but the standard library — runs in the leanest CI leg.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: wrapper keys every capture file must carry
WRAPPER_KEYS = ("n", "cmd", "rc")
#: bench-line keys a non-null `parsed` must carry
PARSED_KEYS = ("metric", "value", "unit", "vs_baseline")

COLUMNS = (
    ("run", "run"),
    ("mray_per_sec", "Mray/s"),
    ("vs_baseline", "vs 100"),
    ("occupancy", "occupancy"),
    ("roofline", "roofline"),
    ("overlap", "overlap"),
    ("hbm_headroom", "hbm_headroom"),
    ("mse", "mse"),
    ("outage", "outage"),
    ("flight_phase", "flight_phase"),
)


def load_capture(path: str) -> Dict[str, Any]:
    """One BENCH file -> a flat trajectory row. Raises ValueError on
    schema drift (the CI gate's failure mode)."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: top level is not an object")
    for k in WRAPPER_KEYS:
        if k not in doc:
            raise ValueError(f"{path}: wrapper missing {k!r}")
    parsed = doc.get("parsed")
    if parsed is not None and not isinstance(parsed, dict):
        raise ValueError(f"{path}: parsed is neither null nor an object")
    row: Dict[str, Any] = {
        "n": int(doc["n"]),
        "run": f"r{int(doc['n']):02d}",
        "rc": doc["rc"],
        "file": os.path.basename(path),
    }
    if parsed is None:
        # rc != 0 with no parseable line: the pre-PR-2 failure shape —
        # report it as an outage-class row rather than dropping the run
        row |= {
            "mray_per_sec": None, "vs_baseline": None, "occupancy": None,
            "roofline": None, "overlap": None, "hbm_headroom": None,
            "mse": None, "outage": True,
            "flight_phase": None,
            "error": "no parseable bench line",
        }
        return row
    for k in PARSED_KEYS:
        if k not in parsed:
            raise ValueError(f"{path}: parsed line missing {k!r}")
    if parsed["unit"] != "Mray/s":
        raise ValueError(f"{path}: unexpected unit {parsed['unit']!r}")
    tel = parsed.get("telemetry") or {}
    outage = bool(parsed.get("infra_outage")) or (
        # pre-PR-4 outage lines lack the flag but carry value 0 + error
        parsed["value"] == 0.0 and bool(parsed.get("error"))
    )
    row |= {
        "mray_per_sec": parsed["value"],
        "vs_baseline": parsed["vs_baseline"],
        "occupancy": parsed.get("mean_wave_occupancy"),
        "roofline": tel.get("live_vs_static_ratio"),
        # pipelined-dispatch host overlap (ISSUE 13): device_wait /
        # measured wall — absent from pre-PR-13 captures, rendered as
        # "—" rather than schema drift
        "overlap": tel.get("host_overlap_fraction"),
        # hbmcheck serve-model HBM budget fraction free (ISSUE 18) —
        # absent from pre-PR-18 captures, rendered as "—"
        "hbm_headroom": parsed.get("hbm_headroom"),
        "mse": parsed.get("mse_vs_cpu_ref"),
        "outage": outage,
        "flight_phase": parsed.get("flight_phase"),
    }
    if parsed.get("error"):
        row["error"] = str(parsed["error"])[:160]
    return row


def _cell(v: Any) -> str:
    if v is None:
        return "—"
    if isinstance(v, bool):
        return "yes" if v else ""
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def to_markdown(rows: List[Dict[str, Any]]) -> str:
    lines = [
        "# Bench trajectory",
        "",
        "Generated by `python tools/bench_report.py` from the committed "
        "`BENCH_r*.json` capture files — regenerate after adding one.",
        "",
        "| " + " | ".join(h for _, h in COLUMNS) + " |",
        "|" + "|".join("---" for _ in COLUMNS) + "|",
    ]
    for row in rows:
        lines.append(
            "| " + " | ".join(_cell(row.get(k)) for k, _ in COLUMNS) + " |"
        )
    notes = [r for r in rows if r.get("error")]
    if notes:
        lines.append("")
        for r in notes:
            lines.append(f"- **{r['run']}**: {r['error']}")
    lines.append("")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="tools/bench_report.py")
    ap.add_argument(
        "files", nargs="*",
        help="BENCH capture files (default: BENCH_r*.json in the repo root)",
    )
    ap.add_argument("--json", action="store_true",
                    help="emit the rows as JSON instead of markdown")
    ap.add_argument("--out", default="",
                    help="also write the report to this file")
    args = ap.parse_args(argv)
    files = args.files or sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    if not files:
        print("bench_report: no BENCH_r*.json captures are committed")
        return 0
    rows = []
    drift = 0
    for path in files:
        try:
            rows.append(load_capture(path))
        except (ValueError, OSError, KeyError) as e:
            drift += 1
            print(f"SCHEMA DRIFT {e}", file=sys.stderr)
    # numeric: the zero-padded run string would sort r100 before r99
    rows.sort(key=lambda r: r["n"])
    report = (
        json.dumps(rows, indent=2) if args.json else to_markdown(rows)
    )
    print(report)
    if args.out:
        # byte-identical to the stdout redirect (print appends "\n"), so
        # --out and `> file` regenerate the same committed BENCH_REPORT.md
        # and the CI diff gate can't flag a just-regenerated file as stale
        with open(args.out, "w") as f:
            f.write(report + "\n")
    return 1 if drift else 0


if __name__ == "__main__":
    sys.exit(main())
