"""A sum of the program's compile counters over set-up or the window."""


def read(ctx, phase, names):
    return float(sum(ctx["counters"][phase][n] for n in names))
