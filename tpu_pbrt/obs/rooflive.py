"""Live-vs-static roofline cross-check.

PR 3's jaxcost computes STATIC per-wave costs (bytes/FLOPs of one pool
drain wave, committed in analysis/budgets.json and emitted into every
BENCH JSON as static_bytes_per_wave / static_flops_per_wave). This
module closes the loop with the LIVE side: a capture measures how many
waves ran and how long they took, so

    live_bytes_per_sec = static_bytes_per_wave * waves / seconds

is the HBM bandwidth the drain actually sustained under the static
model, and dividing by the platform's peak HBM bandwidth gives the
roofline fraction — the `live_vs_static_ratio` next to the static
fields in the bench JSON. Readings:

- ratio near 1: the drain is HBM-bound exactly as the static model says
  (further wins need fewer bytes/wave, not scheduling);
- ratio << 1: waves are NOT paying their modeled bytes — occupancy,
  launch latency, or host stalls dominate (scheduling problem);
- ratio > 1: the static model over-counts (fusion is eliminating
  modeled traffic) — refresh the model's assumptions.

The ratio is null on a CPU capture (the static half still carries the
signal). A TPU whose `device_kind` is not in the table below is an
error, not a null: a capture from the chip without its roofline share
must say why, not print a blank.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Optional

#: peak HBM bandwidth per chip, bytes/s (public TPU spec sheets; used
#: only to normalize the live-implied bandwidth into a roofline fraction).
#: Keys are matched as substrings of jax's lowercased `device_kind`; a
#: v5e reports "TPU v5 lite" (my chip run, PR 21).
PLATFORM_HBM_BYTES_PER_SEC = {
    "v2": 700e9,
    "v3": 900e9,
    "v4": 1228e9,
    "v5e": 819e9,
    "v5 lite": 819e9,
    "v5p": 2765e9,
    "v6e": 1640e9,
    "trillium": 1640e9,
}


def platform_hbm_peak(device_kind: Optional[str]) -> Optional[float]:
    """Peak HBM bytes/s for a jax device_kind string (substring match,
    longest key wins so "v5 lite"/"v5e" beat "v5"). None for a device
    that is not a TPU; a TPU that is not in the table raises."""
    if not device_kind:
        return None
    kind = device_kind.lower()
    best = None
    for key, peak in PLATFORM_HBM_BYTES_PER_SEC.items():
        if key in kind and (best is None or len(key) > len(best[0])):
            best = (key, peak)
    if best is None and kind.startswith("tpu"):
        raise ValueError(
            f"unknown TPU device_kind {device_kind!r}: add its HBM peak to "
            "PLATFORM_HBM_BYTES_PER_SEC (tpu_pbrt/obs/rooflive.py)"
        )
    return best[1] if best else None


def live_vs_static(
    *,
    waves: Optional[int],
    seconds: Optional[float],
    static_bytes_per_wave: Optional[int] = None,
    static_flops_per_wave: Optional[int] = None,
    device_kind: Optional[str] = None,
    n_devices: int = 1,
) -> Dict[str, Any]:
    """The bench-JSON telemetry fields. Missing inputs null out the
    dependent fields; an unknown TPU device_kind raises (see
    platform_hbm_peak)."""
    out: Dict[str, Any] = {
        "live_bytes_per_sec": None,
        "live_flops_per_sec": None,
        "hbm_peak_bytes_per_sec": None,
        "live_vs_static_ratio": None,
    }
    if not waves or not seconds or seconds <= 0:
        return out
    wave_rate = waves / seconds
    if static_bytes_per_wave:
        out["live_bytes_per_sec"] = static_bytes_per_wave * wave_rate
    if static_flops_per_wave:
        out["live_flops_per_sec"] = static_flops_per_wave * wave_rate
    peak = platform_hbm_peak(device_kind)
    if peak and out["live_bytes_per_sec"]:
        total_peak = peak * max(n_devices, 1)
        out["hbm_peak_bytes_per_sec"] = total_peak
        out["live_vs_static_ratio"] = round(
            out["live_bytes_per_sec"] / total_peak, 6
        )
    return out


def load_static_budget(
    entry: str = "pool_chunk", budgets_path: Optional[str] = None
) -> Dict[str, Any]:
    """The committed static budget for an entry point (fallback when a
    caller has no bench-shaped static trace at hand). Returns {} when
    the file or entry is missing — advisory, never fatal."""
    path = (
        Path(budgets_path)
        if budgets_path
        else Path(__file__).resolve().parent.parent / "analysis" / "budgets.json"
    )
    try:
        doc = json.loads(path.read_text())
    except (OSError, ValueError):
        return {}
    return dict(doc.get("entries", {}).get(entry, {}))
