"""How `correct` is decided: the timed step against the plain reference.

Set-up drives the compiled step from the seed through its first three
steps, on three distinct batches of the cell's ring, and keeps three
readings of them:
  * the loss of each step;
  * per parameter leaf, the norm of the first step's change (the first
    gradient as the optimizer applied it, times the learning rate);
  * per leaf, the norm of the change after three steps.
The reference (`reference/<family>.py`) follows the same three steps from
the same weights and batches once the window has closed.  Three numbers
are compared, each with a limit from the cell file:
  * `loss_gap`: the largest |loss - reference loss| / reference loss;
  * `grad_gap`, `change_gap`: per counted leaf, |program norm - reference
    norm| divided by the larger of that leaf's reference norm and the median
    leaf's; the number compared is the median over the leaves.
A leaf counts unless the reference's raw first gradient on it is under a
thousandth of the median leaf's (a gradient that is nought to rounding).

Why the median leaf and not the worst: CQ divides each leaf's gradient by
R = 2^round(log2 max|g|), so where max|g| lies near a rounding edge the
program and the reference round R apart and that leaf's whole update
differs by a factor of two.  The errors are quantized to 8 bits against
their largest element, so two sound computations differ in max|g| by a few
percent, and on some seeds one leaf of ~60 flips: the worst leaf then reads
0.5 or 1 on sound runs.  The worst-leaf readings are still printed
(`*_worst`), but not compared.
"""
from __future__ import annotations

import functools
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as T

STEPS = 3
MIN_GRAD_SHARE = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "change_gap")


@jax.jit
def leaf_norms(a, b):
    """Per-leaf Euclidean norm of a - b."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x - y)))
                      for x, y in zip(jax.tree.leaves(a),
                                      jax.tree.leaves(b))])


@jax.jit
def _norms(a):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                      for x in jax.tree.leaves(a)])


@jax.jit
def _amaxes(a):
    return jnp.stack([jnp.max(jnp.abs(x)) for x in jax.tree.leaves(a)])


def reference(config):
    return importlib.import_module(f"bench.reference.{config['family']}")


def init_params(config, seed: int):
    """The cell's starting weights, made on the device in one call."""
    return _init_fn(json.dumps(config))(
        jax.random.fold_in(T.seed_key(seed), 0))


@functools.lru_cache(maxsize=None)
def _init_fn(config_json: str):
    config = json.loads(config_json)
    ref = reference(config)
    return jax.jit(lambda k: ref.init_params(config, k))


def lowered_widths(config, to_bits: int = 4, from_bits: int = 8):
    """The configuration with every `from_bits` width at `to_bits` (int4 is
    the step under int8 that the control ladder names; int7 is a finite
    one where int4 gives no number)."""
    quant = {k: (to_bits if v == from_bits else v)
             for k, v in config["quant"].items()}
    return dict(config, quant=quant)


def lowered_float32(config):
    """The configuration with its float32 layers at JAX's default
    precision: one bfloat16 pass on a TPU."""
    return dict(config, float32_precision="default")


def reference_readings(config, cell, traffic, seed: int, *, half=False):
    """Losses and per-leaf norms of the reference's first three steps
    (`a1`: per-leaf largest |raw gradient| of the first step).

    half=True leaves out the second half of every batch (a planted fault:
    the mean is taken over the rest)."""
    p0 = init_params(config, seed)
    batches = T.make_ring(config, traffic, seed)[:STEPS]
    if half:
        batches = [jax.tree.map(lambda x: x[:x.shape[0] // 2], b)
                   for b in batches]
    step = _reference_step(json.dumps(config), cell["n_shards"])
    key = jax.random.fold_in(T.seed_key(seed), 2)
    p, acc, losses = p0, jax.tree.map(jnp.zeros_like, p0), []
    for i in range(STEPS):
        p, acc, loss, grads = step(p, acc, batches[i],
                                   jax.random.fold_in(key, i))
        losses.append(loss)
        if i == 0:
            d1, g1, a1 = leaf_norms(p, p0), _norms(grads), _amaxes(grads)
    d3 = leaf_norms(p, p0)
    out = jax.device_get({"loss": jnp.stack(losses), "d1": d1, "d3": d3,
                          "g1": g1, "a1": a1})
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


@functools.lru_cache(maxsize=None)
def _reference_step(config_json: str, n_shards: int):
    """The jitted reference step, built once per configuration."""
    config = json.loads(config_json)
    ref = reference(config)
    return jax.jit(lambda p, a, b, k: ref.train_step(
        config, p, a, b, k, n_shards=n_shards))


def _leaf_gaps(prog, ref, counted):
    denom = np.maximum(ref, np.median(ref[counted]))
    return np.abs(prog - ref)[counted] / denom[counted]


def gaps(prog: dict, ref: dict) -> dict:
    counted = ref["g1"] >= MIN_GRAD_SHARE * np.median(ref["g1"])
    d1 = _leaf_gaps(prog["d1"], ref["d1"], counted)
    d3 = _leaf_gaps(prog["d3"], ref["d3"], counted)
    return {
        "loss_gap": float(np.max(np.abs(prog["loss"] - ref["loss"])
                                 / np.abs(ref["loss"]))),
        "grad_gap": float(np.median(d1)),
        "change_gap": float(np.median(d3)),
        "grad_gap_worst": float(np.max(d1)),
        "change_gap_worst": float(np.max(d3)),
        "leaves_counted": int(counted.sum()),
    }


def judge(g: dict, limits: dict):
    """(correct, checks): every number beside its limit; a number that is
    not finite fails."""
    checks = {n: {"value": g[n], "limit": limits[n]} for n in NUMBERS}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
