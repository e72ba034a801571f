"""Device busy time of the traced frame (union of device-op intervals, mean
over the devices) over that frame's waves. On a mesh every device drains its
own share, so the waves are the per-device mean."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["on_device"] or not tr["whole_frame"]:
        return None  # a trace that was stopped inside the frame holds no whole number of waves
    f = ctx["frames"][ctx["traced_index"]]
    waves = f["stats"].get("n_waves")
    spread = ((f["stats"].get("telemetry") or {}).get("wave_spread") or {})
    if spread.get("mean"):
        waves = spread["mean"]
    return 1e3 * tr["busy_s"] / waves if waves else None
