"""(max - min) / mean of the per-device wave counts
(telemetry.wave_spread.rel_spread), mean over the window's frames. Nothing to
read on one device."""


def read(ctx):
    s = []
    for f in ctx["frames"]:
        ws = ((f.get("stats") or {}).get("telemetry") or {}).get("wave_spread") or {}
        if f["ok"] and len(ws.get("per_device_waves", [])) > 1:
            s.append(ws["rel_spread"])
    return 100.0 * sum(s) / len(s) if s else None
