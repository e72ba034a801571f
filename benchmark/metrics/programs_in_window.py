"""Programs made ready between the window's start and its end. Anything but
0 is a finding."""


def read(ctx):
    return ctx["compiles_after"]["programs"] - ctx["compiles_before"]["programs"]
