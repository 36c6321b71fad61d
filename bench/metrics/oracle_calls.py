"""Traced calls of the step that took an XLA oracle in place of a Pallas
kernel on the TPU (layer: kernels/ops dispatch).

The program's own counter (`dispatch_report()["oracle_on_tpu"]`), summed
after the step is traced once.  Moves `samples_per_s`: each oracle call is
a shape for which no kernel runs.
"""
UNIT = "calls"


def read(ctx):
    return ctx.get("oracle_calls")
