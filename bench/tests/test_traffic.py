"""The traffic generator: image rings as before token traffic came, and
token rings of next-token pairs."""
import hashlib
import os

import jax
import numpy as np
import pytest

from bench import spec
from bench import traffic as T
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2 ** 33 + 12345          # as test_rehearsal's

# sha256 of the reduced ResNet cell's ring at SEED, as the generator gave it
# before traffic files named a kind (computed on the CPU).
IMAGE_RING_SHA256 = \
    "89806150f7bfa1eec14e6c641bd19e774c8432006914a7e70155c67aeeb83e91"


def _digest(ring):
    h = hashlib.sha256()
    for batch in ring:
        for k in sorted(batch):
            a = np.asarray(batch[k])
            for part in (k, str(a.dtype), str(a.shape)):
                h.update(part.encode())
            h.update(a.tobytes())
    return h.hexdigest()


def test_image_ring_is_unchanged():
    _, config, traffic = spec.resolve("tiny18.b8", DATA)
    assert "kind" not in traffic
    assert _digest(T.make_ring(config, traffic, SEED)) == IMAGE_RING_SHA256
    named = dict(traffic, kind="images")
    assert _digest(T.make_ring(config, named, SEED)) == IMAGE_RING_SHA256


def test_token_ring_is_next_token_pairs():
    _, config, traffic = spec.resolve("tinylm.t32", DATA)
    ring = T.make_ring(config, traffic, SEED)
    assert len(ring) == traffic["ring"] == 3
    for batch in ring:
        assert set(batch) == {"tokens", "labels"}
        for x in batch.values():
            assert x.shape == (2, 32) and x.dtype == np.int32
        tok, lab = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        np.testing.assert_array_equal(tok[:, 1:], lab[:, :-1])
        assert tok.min() >= 0 and lab.max() < config["vocab"]
    assert not np.array_equal(ring[0]["tokens"], ring[1]["tokens"])
    again = T.make_ring(config, traffic, SEED)
    for a, b in zip(jax.tree.leaves(ring), jax.tree.leaves(again)):
        np.testing.assert_array_equal(a, b)


def test_unknown_kind_is_refused():
    _, config, traffic = spec.resolve("tinylm.t32", DATA)
    with pytest.raises(SystemExit, match="unknown traffic kind 'packed'"):
        T.make_ring(config, dict(traffic, kind="packed"), SEED)

