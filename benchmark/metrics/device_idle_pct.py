"""1 - busy union over the traced frame, mean over the cell's devices."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["on_device"] or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
