"""Record the small TPU trace that tests/test_trace.py reads.

    python3 bench/tests/record_trace.py        # on a machine with a TPU

Traces three calls of a small jitted program that holds a convolution
fused with a reduction, the program's int8 quantize kernel and the CQ
kernel, inside the benchmark's own host spans, and writes
`data/small.xplane.pb` and `data/small.hlo.txt` (the compiled module, from
which trace.categories names the ops).
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops  # noqa: E402


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace needs a TPU")
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 256))
    bits = jax.random.bits(jax.random.PRNGKey(1), (512, 256), jnp.uint32)
    img = jax.random.normal(jax.random.PRNGKey(2), (8, 16, 16, 32))
    w = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 32, 32))

    def small(x, bits, img, w):
        q = ops.quantize_op(x, jnp.float32(64.0))
        c = ops.cq_op(x, bits, jnp.float32(64.0))
        y = jax.lax.conv_general_dilated(
            img, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return q, c, jnp.sum(jax.nn.relu(y))

    compiled = jax.jit(small).lower(x, bits, img, w).compile()
    jax.block_until_ready(compiled(x, bits, img, w))
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    out = compiled(x, bits, img, w)
                with jax.profiler.TraceAnnotation("bench.batch"):
                    time.sleep(0.002)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        os.makedirs(os.path.join(HERE, "data"), exist_ok=True)
        shutil.copy(path, os.path.join(HERE, "data", "small.xplane.pb"))
        with open(os.path.join(HERE, "data", "small.hlo.txt"), "w") as f:
            f.write(compiled.as_text())
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
