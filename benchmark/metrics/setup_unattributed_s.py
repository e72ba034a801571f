"""`setup_s` less the UNION of the program's spans between the process's
start and the window's start: imports, device start, the benchmark's scene
writer, and whatever the program does under no span. The recorder's clock is
placed on `ctx["t_start"]`'s (`time.monotonic`) by one paired reading of
both, as `TRACE.now` allows; nothing to read where the recorder has no such
reading. `render/slice` is left out: an async span, a dispatch's time in
flight and not something the host does."""

import time

IN_FLIGHT = ("render/slice",)


def covered_s(ctx):
    """Seconds of [t_start, t_start + setup_s] under at least one span, or
    None where the spans cannot be placed."""
    from tpu_pbrt.obs.trace import TRACE

    now = getattr(TRACE, "now", None)
    if now is None:
        return None
    offset = time.monotonic() - now()
    t0, t1 = ctx["t_start"], ctx["t_start"] + ctx["setup_s"]
    covered, end = 0.0, t0
    for a, b in sorted((s.start + offset, s.start + offset + s.seconds)
                       for s in TRACE.spans() if s.name not in IN_FLIGHT):
        a, b = max(a, end), min(b, t1)
        if b > a:
            covered, end = covered + b - a, b
    return covered


def read(ctx):
    covered = covered_s(ctx)
    return None if covered is None else ctx["setup_s"] - covered
