"""Share of the ray slots the stream tracer's flush ran that held a (ray,
treelet) leaf test: telemetry counters stream_leaf_tests over
stream_block_slots, over the window's frames. A trip of the flush's chunk
loop pays for every slot of its blocks, filled or not. Nothing to read where
the program does not count the slots."""


def read(ctx):
    tests = slots = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and "stream_leaf_tests" in c and c.get("stream_block_slots"):
            tests += c["stream_leaf_tests"]
            slots += c["stream_block_slots"]
    return 100.0 * tests / slots if slots else None
