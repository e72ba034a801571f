"""What the light layer holds on the device, in MB (10^6 bytes): the entries
`light` (the light table, its packed rows, the per-light corners) and
`light_pick` (the strategy's per-voxel table, where it is an argument of the
program) of the `scene/upload` span's `scene_resident_bytes`, for the last
scene this process uploaded. Nothing to read where the span carries no such
fact or the program keeps no strategy table on the device by that name."""

TABLES = ("light", "light_pick")


def read(ctx):
    from tpu_pbrt.obs.trace import TRACE

    spans = getattr(TRACE, "spans", None)
    for span in reversed(spans("scene/upload") if spans else []):
        tables = (getattr(span, "args", None) or {}).get("scene_resident_bytes")
        if tables:
            return sum(tables.get(k, 0) for k in TABLES) / 1e6 if "light_pick" in tables else None
    return None
