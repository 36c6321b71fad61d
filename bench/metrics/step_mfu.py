"""The whole step's share of the chip's int8 peak, from the trace (layer:
device).

Model FLOPs of the traced steps on one chip (the family's work file,
`model_flops_per_sample` for the traffic's samples: for a ResNet 3 x the
forward conv and FC FLOPs of an image; times the chip's share of the batch)
over the traced window and the int8 peak, in %.  It bounds every kernel's
roofline claim: a kernel taken off the path leaves its own roofline silent,
this one does not.  Moves `samples_per_s`.
"""
from bench.work import for_config

UNIT = "%"


def read(ctx):
    if ctx["peaks"] is None or ctx["window_s"] <= 0:
        return None
    per_chip = ctx["traffic"]["batch"] // ctx["chips"]
    work = for_config(ctx["config"])
    flops = work.model_flops_per_sample(ctx["config"], ctx["traffic"]) \
        * per_chip * ctx["steps"]
    return 100.0 * flops / ctx["window_s"] / ctx["peaks"]["int8_ops"]
