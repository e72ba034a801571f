"""What the compiled scene holds on its device, in MB (10^6 bytes): the
program's own `scene_resident_bytes` on its `scene/upload` span, bytes by
table as the device lays them out, summed over the tables of the last scene
this process uploaded. Nothing to read where the program's spans carry no
such fact."""


def read(ctx):
    from tpu_pbrt.obs.trace import TRACE

    spans = getattr(TRACE, "spans", None)
    for span in reversed(spans("scene/upload") if spans else []):
        tables = (getattr(span, "args", None) or {}).get("scene_resident_bytes")
        if tables:
            return sum(tables.values()) / 1e6
    return None
