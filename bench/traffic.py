"""The one generator of training traffic: a ring of distinct batches made
on the device from the seed.

A traffic file gives `batch` (samples per step, across all chips) and
`ring` (distinct batches kept on the device; step i trains on batch
i mod ring).  Images are N(0, 1) pixels at the configuration's size,
labels uniform over its classes.  The same seed gives the same ring.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def batch_at(config, traffic, key, i):
    k_img, k_lab = jax.random.split(jax.random.fold_in(key, i))
    b, s = traffic["batch"], config["img_size"]
    return {"images": jax.random.normal(k_img, (b, s, s, 3), jnp.float32),
            "labels": jax.random.randint(k_lab, (b,), 0,
                                         config["num_classes"], jnp.int32)}


def make_ring(config, traffic, seed: int, out_shardings=None):
    """All `ring` batches in one jitted call on the device."""
    key = jax.random.fold_in(seed_key(seed), 1)

    def gen(key):
        return [batch_at(config, traffic, key, i)
                for i in range(traffic["ring"])]

    return jax.jit(gen, out_shardings=out_shardings)(key)
