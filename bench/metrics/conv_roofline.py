"""Share of the convolutions' roofline (layer: core.qconv).

Least time of one step's convolution work on one chip (the family's work
file, `conv_work`: for a ResNet the forward, weight and input gradients at
the chip's share of the batch), over the convolutions' device time per step
(as in conv_ms_per_step), in %.  The least time is the larger of two
bounds: FLOPs over the int8 peak, and int8 operand bytes plus float32
result bytes over the HBM bandwidth.  The byte bound binds in both ResNet
cells (the float32 results dominate): ResNet-50 at batch 64 needs 10.27 ms
by bytes against 3.96 ms by FLOPs, ResNet-18 at batch 8 0.342 ms against
0.217 ms.  Nothing where the work file has no `conv_work`.  Moves
`samples_per_s`.
"""
from bench.trace import op_seconds
from bench.work import for_config

UNIT = "%"


def read(ctx):
    t = op_seconds(ctx, lambda name, cat: cat == "conv")
    conv_work = getattr(for_config(ctx["config"]), "conv_work", None)
    if t is None or ctx["peaks"] is None or conv_work is None:
        return None
    per_chip = ctx["traffic"]["batch"] // ctx["chips"]
    flops, nbytes = conv_work(ctx["config"], ctx["traffic"], per_chip)
    least = max(flops / ctx["peaks"]["int8_ops"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (t / ctx["steps"])
