"""Device idle share of the traced window (layer: device).

1 - (union of the device's op intervals) / (traced window), averaged over
the chips, in %.  Moves `samples_per_s`: an idle device trains nothing.
"""
UNIT = "%"


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
