"""Self time of ops that XLA names a sort, over device busy time, in the
traced frame."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["on_device"] or not tr["busy_s"] or not tr["sort_s"]:
        return None
    return 100.0 * tr["sort_s"] / tr["busy_s"]
