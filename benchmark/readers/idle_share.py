"""The device's idle share of the traced window, busiest device."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share()
