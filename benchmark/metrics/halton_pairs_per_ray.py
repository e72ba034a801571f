"""2D draws of the halton sampler that live lanes' bounces made (telemetry
counter halton_pairs: two a live lane a wave, the light's uv and the BSDF's
uv, each a pair of scrambled radical inverses) per ray traced, over the
window's frames. It moves only if the sampler's dimension layout or the
paths' lengths do. Nothing to read where the program does not count them
(another sampler, or a program without the counter)."""


def read(ctx):
    pairs = rays = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and "halton_pairs" in c and f.get("rays_traced"):
            pairs += c["halton_pairs"]
            rays += f["rays_traced"]
    return pairs / rays if rays else None
