"""Self time of collective ops (the drain's all-reduce and the film psum:
transfer and the wait for the slowest device) per dispatch, mean over the
devices. A dispatch is a program that lies wholly inside the traced window
and holds a collective; collectives of a program the trace cuts are not
counted, so the value does not move with the traffic's `trace_seconds`. Per
dispatch, not per frame: the mesh cell's trace is stopped before the frame
ends, and a dispatch is the unit that holds exactly one film psum. Nothing
to read where no device traced a whole dispatch with a collective."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["on_device"] or not tr["collective_s_per_dispatch"]:
        return None
    per_device = tr["collective_s_per_dispatch"]
    return 1e3 * sum(per_device) / len(per_device)
