"""(max - min) / mean of the rays each device traced
(telemetry.ray_spread.rel_spread), mean over the window's frames: the twin of
wave_spread_pct, which counts a wave of rays that miss everything like a wave
that meets the mesh. Nothing to read on one device, or from a program that
does not send the count out."""


def read(ctx):
    s = []
    for f in ctx["frames"]:
        rs = ((f.get("stats") or {}).get("telemetry") or {}).get("ray_spread") or {}
        if f["ok"] and len(rs.get("per_device_rays", [])) > 1:
            s.append(rs["rel_spread"])
    return 100.0 * sum(s) / len(s) if s else None
