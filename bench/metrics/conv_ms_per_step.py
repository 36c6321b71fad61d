"""Device time of the convolutions per step (layer: core.qconv).

Ops whose compiled HLO holds a `convolution` (alone or inside a fusion,
see trace.categories) count; their time per traced step, in ms, averaged
over the chips.  Moves `samples_per_s`.
"""
from bench.trace import op_seconds

UNIT = "ms"


def read(ctx):
    t = op_seconds(ctx, lambda name, cat: cat == "conv")
    return None if t is None else 1e3 * t / ctx["steps"]
