"""Self time of collective ops inside whole dispatches over the duration of
those dispatches, mean over the devices: what a device spends in the drain's
all-reduce and the film psum, most of it waiting for the slowest device (the
trace counts that wait as busy, so `device_idle_pct` does not see it). Whole
dispatches as in `collective_ms_per_dispatch`: the wait comes at a
dispatch's end, so a share of a window that cuts a dispatch would move with
the cut. Nothing to read where no device traced a whole dispatch with a
collective."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["on_device"] or not tr["collective_share"]:
        return None
    per_device = tr["collective_share"]
    return 100.0 * sum(per_device) / len(per_device)
