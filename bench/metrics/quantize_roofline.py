"""Share of the quantize kernel's roofline (layer: kernels/quantize).

Elements one step quantizes to int8 on one chip (the family's work file,
`quantized_elements`: for a ResNet Q_W and Q_A), times 4 bytes in
(float32, the width the data path hands the kernel) plus 1 byte out, over
the HBM bandwidth; over the kernel's device time per step (as in
quantize_ms_per_step), in %.  A pass that only moves bytes is bound by
bandwidth.  Nothing where the work file has no `quantized_elements`.
Moves `samples_per_s`.
"""
from bench.trace import op_seconds
from bench.work import for_config

UNIT = "%"
KERNEL = "quantize_fused"
BYTES_PER_ELEMENT = 4 + 1


def read(ctx):
    t = op_seconds(ctx, lambda name, cat: cat.startswith("kernel:")
                   and KERNEL in name)
    elements = getattr(for_config(ctx["config"]), "quantized_elements",
                       None)
    if t is None or ctx["peaks"] is None or elements is None:
        return None
    per_chip = ctx["traffic"]["batch"] // ctx["chips"]
    n = elements(ctx["config"], ctx["traffic"], per_chip)
    least = n * BYTES_PER_ELEMENT / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (t / ctx["steps"])
