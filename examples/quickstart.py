"""Quickstart: train a tiny LM fully in 8-bit integers (WAGEUBN) on CPU.

    PYTHONPATH=src python examples/quickstart.py

Shows the public API end to end: config -> model -> quantized train step ->
losses under FP32 vs full-INT8 side by side.
"""
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import preset
from repro.data import TokenTask
from repro.launch.train import make_train_step
from repro.models import build_model
from repro.optim import init_momentum

ARCH = ArchConfig(name="quickstart", family="lm", n_layers=2, d_model=64,
                  n_heads=4, n_kv=2, d_ff=128, vocab=64, head_dim=16,
                  q_chunk=32, kv_chunk=32)


def train(qcfg, steps=60):
    model = build_model(ARCH, qcfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = init_momentum(params)
    step_fn = jax.jit(make_train_step(model, qcfg, model.labels(params)))
    task = TokenTask(vocab=ARCH.vocab, seq_len=32, global_batch=8)
    hist = []
    for s in range(steps):
        batch = jax.tree.map(jnp.asarray, task.batch(s))
        params, opt, m = step_fn(params, opt, batch, jnp.int32(s))
        hist.append(float(m["loss"]))
    return hist


if __name__ == "__main__":
    from repro.core import registered_quantizers
    from repro.kernels.ops import dispatch_banner
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    print(dispatch_banner())
    print("registered quantizers:", ", ".join(registered_quantizers()))
    print("training the same tiny LM under four numeric configs...")
    for name, mode in (("fp32", None), ("e2_16", "sim"), ("full8", "sim"),
                       ("full8", "native")):
        qcfg = preset(name, mode)
        hist = train(qcfg)
        label = name if mode in (None, "sim") else f"{name}/{mode}"
        print(f"{label:12s} loss: {hist[0]:.3f} -> {hist[-1]:.3f} "
              f"(min {min(hist):.3f})")
    print("\nWAGEUBN full-INT8 training tracks FP32 — the paper's core claim."
          "\n(native mode carries int8 QTensor payloads end to end; sim mode"
          "\ncarries the same grid values in fp32 — bit-identical forward.)")
