"""Deterministic, shardable synthetic data pipelines.

Every batch is a pure function of (seed, step, sample-index), so the
pipeline is elastic by construction: any host can materialize exactly its
slice of the global batch for any step (crash/restart, re-scale, or
straggler re-assignment never changes the data stream).  A background
prefetch thread overlaps host data generation with device compute.

Tasks:
  TokenTask  — "arith" (learnable: next token is a fixed affine function of
               the previous two, mod vocab — a convergence probe for the
               paper's accuracy experiments) or "uniform" (pure throughput).
  ImageTask  — class-conditional Gaussian blobs (learnable) for the ResNet
               reproduction.
"""
from __future__ import annotations

import functools
import queue
import threading
from dataclasses import dataclass

import jax
import numpy as np


def host_local_slice(global_batch: int, shard_idx: int, n_shards: int):
    per = global_batch // n_shards
    return shard_idx * per, per


@dataclass
class TokenTask:
    vocab: int
    seq_len: int
    global_batch: int
    kind: str = "arith"          # arith | uniform
    seed: int = 0

    def sample(self, step: int, start: int, count: int) -> dict:
        rs = np.random.RandomState(
            (self.seed * 1_000_003 + step) % (2 ** 31))
        rs.randint(0, 2 ** 30, size=start + 1)  # decorrelate shard offsets
        rs = np.random.RandomState(
            (self.seed * 1_000_003 + step * 7919 + start) % (2 ** 31))
        v, s = self.vocab, self.seq_len
        if self.kind == "uniform":
            toks = rs.randint(0, v, size=(count, s + 1), dtype=np.int32)
        else:
            toks = np.empty((count, s + 1), dtype=np.int32)
            toks[:, 0] = rs.randint(0, v, size=count)
            toks[:, 1] = rs.randint(0, v, size=count)
            a, b, c = 3, 5, 7
            for t in range(2, s + 1):
                toks[:, t] = (a * toks[:, t - 1] + b * toks[:, t - 2] + c) % v
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def batch(self, step: int, shard_idx: int = 0, n_shards: int = 1) -> dict:
        start, count = host_local_slice(self.global_batch, shard_idx,
                                        n_shards)
        return self.sample(step, start, count)


@dataclass
class ImageTask:
    img_size: int
    num_classes: int
    global_batch: int
    seed: int = 0

    def batch(self, step: int, shard_idx: int = 0, n_shards: int = 1) -> dict:
        start, count = host_local_slice(self.global_batch, shard_idx,
                                        n_shards)
        rs = np.random.RandomState(
            (self.seed * 1_000_003 + step * 7919 + start) % (2 ** 31))
        labels = rs.randint(0, self.num_classes, size=count).astype(np.int32)
        imgs = (self._protos[labels]
                + 0.8 * rs.randn(count, self.img_size, self.img_size, 3)
                ).astype(np.float32)
        return {"images": imgs, "labels": labels}

    @functools.cached_property
    def _protos(self) -> np.ndarray:
        """Class-conditional means on a fixed random direction per class,
        drawn once per task (600 MB at 1000 classes x 224 px)."""
        proto_rs = np.random.RandomState(self.seed + 12345)
        return proto_rs.randn(self.num_classes, self.img_size,
                              self.img_size, 3).astype(np.float32)

    def holdout_batch(self, i: int) -> dict:
        """Held-out eval batches: fresh steps the model never trains on —
        the same protocol NpzImageTask serves from its val split."""
        return self.batch(10_000 + i)


def make_global_batch(host_batch: dict, mesh, pspec_tree) -> dict:
    """Place a host batch onto the mesh with the given PartitionSpecs.

    Single-process: jax.device_put with NamedSharding.  (On a real multi-host
    pod this becomes jax.make_array_from_process_local_data — same call
    shape, the pipeline code does not change.)
    """
    from jax.sharding import NamedSharding

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree.map(put, host_batch, pspec_tree)


class Prefetcher:
    """Background thread that keeps `depth` batches ready."""

    def __init__(self, fn, start_step: int = 0, depth: int = 2):
        self._fn = fn
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._step = start_step
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._work, daemon=True)
        self._t.start()

    def _work(self):
        step = self._step
        while not self._stop.is_set():
            try:
                self._q.put((step, self._fn(step)), timeout=0.2)
                step += 1
            except queue.Full:
                continue

    def get(self):
        return self._q.get()

    def close(self):
        self._stop.set()
        self._t.join(timeout=2)
