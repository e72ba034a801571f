"""Traversal pairs the stream tracer lost to worklist capacity (telemetry
counter stream_pairs_dropped), summed over the window's frames: 0, or the
frames have false misses. Nothing to read where the program does not count
them."""


def read(ctx):
    seen = [
        c["stream_pairs_dropped"]
        for f in ctx["frames"] if f["ok"]
        for c in [((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}]
        if "stream_pairs_dropped" in c
    ]
    return sum(seen) if seen else None
