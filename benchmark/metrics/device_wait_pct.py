"""phase_seconds.device_wait over the render loops' seconds, summed over the
window's frames: the share of a frame the host spends blocked on the device."""


def read(ctx):
    wait = sum((f["stats"].get("phase_seconds") or {}).get("device_wait", 0.0)
               for f in ctx["frames"] if f["ok"])
    secs = sum(f["render_seconds"] for f in ctx["frames"] if f["ok"])
    return 100.0 * wait / secs if secs and wait else None
