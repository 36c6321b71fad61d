"""CPU rehearsal of bench/calibrate_dp.py on the reduced ResNet's
data-parallel cell: the step runs at dp 1 with the cell's n_shards, and the
faults planted in its batch, the exchange left out among them, come out
as not correct."""
import os

import pytest

from bench import calibrate_dp
from bench import correct as C
from bench import spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2 ** 33 + 777


@pytest.fixture(scope="module")
def readings():
    return calibrate_dp.main(
        ["--workload", "tiny18.dp4", "--seed", str(SEED), "--seeds", "2",
         "--control-seeds", "1"], require_tpu=False, root=DATA)


def _limits():
    return spec.resolve("tiny18.dp4", DATA)[0]["limits"]


def test_sound_rows_are_correct(readings):
    assert readings["run_as"] == {"dp": 1, "chips": 1, "n_shards": 4}
    for row in readings["rows"]["program"]:
        ok, checks = C.judge(row, _limits())
        assert ok, checks


@pytest.mark.parametrize("fault", ["no_exchange", "half_batch"])
def test_faults_are_not_correct(readings, fault):
    ok, checks = C.judge(readings["upper"][fault], _limits())
    assert not ok, checks


def test_narrow_wire_changes_the_step(readings):
    """At 8 bits the wire rounds the gradients apart from the 16-bit
    step's; whether the limits see it is the chip's reading."""
    sound, wire8 = readings["rows"]["program"][0], readings["rows"]["wire8"][0]
    assert wire8["seed"] == sound["seed"]
    assert any(wire8[n] != sound[n] for n in C.NUMBERS)
