"""CPU rehearsals of a whole run at the reduced ResNet: the harness's look
for a chip is skipped, everything else runs.  Faults planted under the
timed step, and the int4 and int7 controls put in the program's place, must
come out as not correct."""
import json
import os
import shutil
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct as C
from bench import program, run, spec

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEED = 2 ** 33 + 12345          # past 32 bits, as the driver's seeds are


def _run(workload, fault=None, seed=SEED):
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", "1", "--trace", "0"])
    return run.run(args, require_tpu=False, fault=fault, root=DATA)


def _numbers_failed(res):
    return [n for n in C.NUMBERS
            if not res["checks"][n]["value"] <= res["checks"][n]["limit"]]


def test_rehearsal_dp1_is_correct():
    res = _run("tiny18.b8")
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"samples_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def _unchanged(step):
    """A step that returns its state unchanged (the loss is still real)."""
    def f(p, o, b, i):
        pc, oc = jax.tree.map(jnp.copy, (p, o))
        _, _, m = step(pc, oc, b, i)
        return p, o, m
    return f


def _half_batch(step):
    """Half of every batch left out: its rows replaced by the first half's,
    so the loss, the BN statistics and the gradient are those of the first
    half alone."""
    def f(p, o, b, i):
        h = b["labels"].shape[0] // 2
        return step(p, o, jax.tree.map(
            lambda x: jnp.concatenate([x[:h], x[:h]]), b), i)
    return f


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["unchanged_state", "half_batch"])
def test_planted_faults_are_not_correct(fault):
    res = _run("tiny18.b8", fault=fault)
    assert not res["correct"]
    assert _numbers_failed(res), res["checks"]


def _control_is_not_correct(lowered, seed):
    cell, config, traffic = spec.resolve("tiny18.b8", DATA)
    ref = C.reference_readings(config, cell, traffic, seed)
    low = C.reference_readings(lowered(config), cell, traffic, seed)
    ok, checks = C.judge(C.gaps(low, ref), cell["limits"])
    assert not ok, checks


@pytest.mark.parametrize("seed", [SEED + 1, SEED + 2, SEED + 3])
def test_int4_control_is_not_correct(seed):
    _control_is_not_correct(C.lowered_widths, seed)


@pytest.mark.parametrize("seed", [SEED + 1, SEED + 2, SEED + 3])
def test_int7_control_is_not_correct(seed):
    """The finite control that the chip cells' limits are held against
    (the float32 layers at one bfloat16 pass, the other, needs a TPU: on
    the CPU every precision computes in float32)."""
    _control_is_not_correct(lambda c: C.lowered_widths(c, 7), seed)


def test_rehearsal_dp4_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (
        "import json, sys; sys.path.insert(0, %r); from bench import run; "
        "a = run.parse(['--workload', 'tiny18.dp4', '--seed', '%d', "
        "'--seconds', '1', '--trace', '0']); "
        "print(json.dumps(run.run(a, require_tpu=False, root=%r)))"
        % (ROOT, SEED, DATA))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"], res["checks"]


def _lm_model(config):
    from repro.core.qconfig import preset
    from repro.models import build_model
    return build_model(program.arch_config(config),
                       preset(config["preset"], config["mode"]))


def _lm_reference():
    """A stand-in for `reference/lm.py`, for the harness only: its weights
    are the program's own `model.init`, its step plain SGD on the program's
    loss.  It is no reference, so the rehearsal judges no `correct`."""
    ref = types.ModuleType("bench.reference.lm")
    ref.init_params = lambda config, key: _lm_model(config).init(key)

    def train_step(config, p, acc, batch, key, n_shards=1):
        (loss, _), g = jax.value_and_grad(_lm_model(config).loss,
                                          has_aux=True)(p, batch, key)
        lr = config["optimizer"]["lr"]
        return jax.tree.map(lambda w, d: w - lr * d, p, g), acc, loss, g

    ref.train_step = train_step
    return ref


def _lm_work():
    """A stand-in for `work/lm.py`: 6 FLOPs a weight of the layers' matmuls
    and the head, and a token.  Plain arithmetic: a work count traces
    nothing, since the window is over when `run.py` asks for it."""
    work = types.ModuleType("bench.work.lm")

    def model_flops_per_sample(config, traffic):
        d, dh, f = config["d_model"], config["head_dim"], config["d_ff"]
        attn = 2 * d * dh * (config["n_heads"] + config["n_kv"])
        layer = attn + 3 * d * f
        return 6 * (config["n_layers"] * layer + d * config["vocab"]) \
            * traffic["seq"]

    work.model_flops_per_sample = model_flops_per_sample
    return work


def test_rehearsal_token_traffic_cell(monkeypatch):
    """A dense decoder on token traffic runs through the whole harness with
    nothing in it but files of its own (and these test stand-ins)."""
    monkeypatch.setitem(sys.modules, "bench.reference.lm", _lm_reference())
    monkeypatch.setitem(sys.modules, "bench.work.lm", _lm_work())
    builds, rings = [], []
    build, ring = program.Program.build, run.traffic_ring

    def counted_build(self, params, batch):
        builds.append(batch)
        return build(self, params, batch)

    def kept_ring(*a, **kw):
        rings.append(ring(*a, **kw))
        return rings[-1]

    monkeypatch.setattr(program.Program, "build", counted_build)
    monkeypatch.setattr(run, "traffic_ring", kept_ring)
    res = _run("tinylm.t32")
    assert len(builds) == 1
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert res["checks"]["nonfinite_losses"]["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"samples_per_s", "setup_s"} <= set(res["metrics"])
    (batches,) = rings
    assert len(batches) == 3
    for b in batches:
        for x in (b["tokens"], b["labels"]):
            assert x.shape == (2, 32) and x.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(b["tokens"])[:, 1:],
                                      np.asarray(b["labels"])[:, :-1])


def test_setup_names_a_missing_work_file(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.reference.lm", _lm_reference())
    monkeypatch.delitem(sys.modules, "bench.work.lm", raising=False)
    with pytest.raises(SystemExit, match=r"no bench/work/lm\.py"):
        _run("tinylm.t32")


def test_ring_shorter_than_the_checked_steps_is_refused(tmp_path):
    for d in ("cells", "configs", "traffic"):
        shutil.copytree(os.path.join(DATA, d), tmp_path / d)
    (tmp_path / "traffic" / "tiny-b8.json").write_text(
        json.dumps({"batch": 8, "ring": C.STEPS - 1}))
    args = run.parse(["--workload", "tiny18.b8", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    with pytest.raises(SystemExit, match="distinct batches"):
        run.run(args, require_tpu=False, root=str(tmp_path))


def _cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet18.b8-online",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _cli(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_benchmark_alone_gives_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = _cli(tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
