"""Device time of a step in operations of some kinds or under a program
scope that a regular expression finds, or in all others (``invert``),
from the trace; averaged over the devices."""


def read(ctx, kinds=(), scope=None, invert=False):
    return ctx["trace"].kind_ms_per_step(set(kinds), scope, invert=invert)
