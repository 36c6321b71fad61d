"""The one generator of training traffic: a ring of distinct batches made
on the device from the seed.

A traffic file gives its `kind`, `batch` (samples per step, across all
chips) and `ring` (distinct batches kept on the device; step i trains on
batch i mod ring).  The same seed gives the same ring.  Kinds:
  * `images` (also a file with no `kind`): N(0, 1) pixels at the
    configuration's `img_size`, labels uniform over its `num_classes`;
  * `tokens`: `seq` + 1 ids a sample, uniform over the configuration's
    `vocab` (the held slice, where the vocabulary is cut); `tokens` are the
    first `seq`, `labels` the last `seq` (the next token of each), int32.
A sample, for `samples_per_s` and the family's work file, is one image or
one sequence of `seq` tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _images(config, traffic, key):
    k_img, k_lab = jax.random.split(key)
    b, s = traffic["batch"], config["img_size"]
    return {"images": jax.random.normal(k_img, (b, s, s, 3), jnp.float32),
            "labels": jax.random.randint(k_lab, (b,), 0,
                                         config["num_classes"], jnp.int32)}


def _tokens(config, traffic, key):
    ids = jax.random.randint(key, (traffic["batch"], traffic["seq"] + 1), 0,
                             config["vocab"], jnp.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


KINDS = {"images": _images, "tokens": _tokens}


def generator(traffic):
    """The generator of the traffic file's `kind`; an unknown kind is
    refused."""
    kind = traffic.get("kind", "images")
    if kind not in KINDS:
        raise SystemExit(f"bench: unknown traffic kind {kind!r}; "
                         f"known: {sorted(KINDS)}")
    return KINDS[kind]


def make_ring(config, traffic, seed: int, out_shardings=None):
    """All `ring` batches in one jitted call on the device."""
    key = jax.random.fold_in(seed_key(seed), 1)
    gen_one = generator(traffic)

    def gen(key):
        return [gen_one(config, traffic, jax.random.fold_in(key, i))
                for i in range(traffic["ring"])]

    return jax.jit(gen, out_shardings=out_shardings)(key)
