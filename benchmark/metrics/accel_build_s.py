"""The program's own spans `accel/sah_build` and `accel/treelet_pack`, summed
by name over this process (set-up only; selected by name, not by time: the
recorder's clock is not ctx["t_start"]'s). Nothing to read where the program
keeps no spans."""

NAMES = ("accel/sah_build", "accel/treelet_pack")


def read(ctx):
    from tpu_pbrt.obs.trace import TRACE

    spans = getattr(TRACE, "spans", None)
    got = [s for s in spans("accel/") if s.name in NAMES] if spans else []
    return sum(s.self_seconds for s in got) if got else None
