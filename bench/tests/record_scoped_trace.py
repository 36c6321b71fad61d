"""Record the small TPU trace that tests/test_layers.py joins to its scopes.

    python3 bench/tests/record_scoped_trace.py    # on a machine with a TPU

Traces three calls of a small jitted program that names its parts as the
training step does: a convolution under `qconv` and a normalization under
`ubn` inside `jax.grad` (so their backward ops hold `transpose(`), a
weight update under `momentum_update`, and the program's int8 quantize
kernel (a `pallas_call` with `name="quantize_fused"`) under `qact`.  Writes
`data/small_scoped.xplane.pb` and `data/small_scoped.hlo.txt` (the compiled
module, whose metadata holds each instruction's op_name).
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops  # noqa: E402


def small(w, img, v):
    def loss(w):
        with jax.named_scope("qconv"):
            y = jax.lax.conv_general_dilated(
                img, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        with jax.named_scope("ubn"):
            y = (y - jnp.mean(y)) / (jnp.std(y) + 1e-3)
        return jnp.sum(jax.nn.relu(y))

    g = jax.grad(loss)(w)
    with jax.named_scope("momentum_update"):
        w = w - 0.01 * g
    with jax.named_scope("qact"):
        q = ops.quantize_op(v, jnp.float32(64.0))
    return w, q


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scoped_trace needs a TPU")
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 3, 32, 32))
    img = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (512, 256))
    compiled = jax.jit(small).lower(w, img, v).compile()
    jax.block_until_ready(compiled(w, img, v))
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    out = compiled(w, img, v)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        os.makedirs(os.path.join(HERE, "data"), exist_ok=True)
        shutil.copy(path, os.path.join(HERE, "data", "small_scoped.xplane.pb"))
        with open(os.path.join(HERE, "data", "small_scoped.hlo.txt"),
                  "w") as f:
            f.write(compiled.as_text())
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
