"""Device time of the int8 quantize kernel per step (layer:
kernels/quantize).

Matches the Pallas kernel `quantize_fused` (it shows in the trace as a
`tpu_custom_call` named after its jitted wrapper, e.g.
`jvp_jit_quantize_fused__.68`): Q_W of every conv weight and Q_A of every
activation.  Time per traced step in ms, averaged over the chips.  Moves
`samples_per_s`.
"""
from bench.trace import op_seconds

UNIT = "ms"
KERNEL = "quantize_fused"


def read(ctx):
    t = op_seconds(ctx, lambda name, cat: cat.startswith("kernel:")
                   and KERNEL in name)
    return None if t is None else 1e3 * t / ctx["steps"]
