"""What the warm-up render waits for the device: the program's
`render/chunk_retire` and `render/wave_drain+film_merge` spans that END
before the window's start. The recorder's clock is placed on
`ctx["t_start"]`'s (`time.monotonic`) by one paired reading of both, as
`TRACE.now` allows; nothing to read where the recorder has no such reading."""

import time

NAMES = ("render/chunk_retire", "render/wave_drain+film_merge")


def read(ctx):
    from tpu_pbrt.obs.trace import TRACE

    now = getattr(TRACE, "now", None)
    if now is None:
        return None
    offset = time.monotonic() - now()
    t_window = ctx["t_start"] + ctx["setup_s"]
    got = [s for s in TRACE.spans("render/")
           if s.name in NAMES and s.start + offset + s.seconds <= t_window]
    return sum(s.seconds for s in got) if got else None
