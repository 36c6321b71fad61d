"""The layer names the program puts into its compiled step.

Every layer boundary of the training step runs under a `jax.named_scope`,
and every Pallas kernel carries its own `name`.  The compiled HLO keeps the
JAX name stack of each instruction as `metadata={op_name="..."}`; a
profiler trace names each device op by its instruction, so these names are
what a trace is read by (PERF.md §3).  A backward op's name stack holds
`transpose(`; the optimizer's holds `momentum_update`.  These tests pin
each scope to the phase it belongs to, by the rule the benchmark reads a
trace with (`bench/layers.py`).  Named scopes are metadata only: no number
is compared here.
"""
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig
from repro.core import preset
from repro.kernels.backward import bwd_dgrad, bwd_wgrad
from repro.kernels.page_gather import page_gather
from repro.kernels.paged_attention import flash_attention, paged_attention
from repro.kernels.qmatmul import qmatmul
from repro.kernels.quantize import cq_stochastic, quantize_fused
from repro.kernels.selective_scan import selective_scan
from repro.kernels.ubn import ubn_norm
from repro.launch.train import make_train_step
from repro.models import build_model
from repro.optim import init_momentum

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
from bench.layers import phase, words  # noqa: E402

_OP_NAME = re.compile(r'op_name="([^"]*)"')

TINY = ArchConfig(name="t-rn", family="resnet", block="basic",
                  stage_sizes=(1,), num_classes=10, img_size=16)


def op_names(hlo_text: str) -> list:
    return _OP_NAME.findall(hlo_text)


def holds(op_name: str, scope: str) -> bool:
    return scope in words(op_name)


@pytest.fixture(scope="module")
def step_names():
    """op_names of the compiled tiny ResNet full8/native step."""
    qcfg = preset("full8", "native")
    model = build_model(TINY, qcfg)
    params = model.init(jax.random.PRNGKey(0))
    fn = make_train_step(model, qcfg, model.labels(params))
    batch = {"images": jnp.zeros((4, 16, 16, 3), jnp.float32),
             "labels": jnp.zeros((4,), jnp.int32)}
    text = jax.jit(fn).lower(params, init_momentum(params), batch,
                             jnp.int32(0)).compile().as_text()
    return op_names(text)


SCOPE_PHASES = [
    ("stem", "forward"), ("stem", "backward"),
    ("stage0", "forward"), ("stage0", "backward"),
    ("block0", "forward"), ("block0", "backward"),
    ("head", "forward"), ("head", "backward"),
    ("qconv", "forward"), ("qconv", "backward"),
    ("q_e2", "backward"),
    ("qact", "forward"), ("q_e1", "backward"),
    ("qweight", "forward"),
    ("ubn", "forward"), ("ubn", "backward"),
    ("amax", "forward"), ("amax", "backward"), ("amax", "optimizer"),
    ("momentum_update", "optimizer"), ("cq", "optimizer"),
    ("update", "optimizer"),
]


@pytest.mark.parametrize("scope,where", SCOPE_PHASES,
                         ids=[f"{s}-{p}" for s, p in SCOPE_PHASES])
def test_scope_is_in_its_phase(step_names, scope, where):
    phases = {phase(n) for n in step_names if holds(n, scope)}
    assert where in phases, (scope, phases)


@pytest.mark.parametrize("scope,only", [
    ("q_e2", "backward"), ("q_e1", "backward"), ("qweight", "forward"),
    ("cq", "optimizer"), ("update", "optimizer")])
def test_scope_is_in_no_other_phase(step_names, scope, only):
    phases = {phase(n) for n in step_names if holds(n, scope)}
    assert phases == {only}, (scope, phases)


def test_q_e2_sits_inside_the_conv_backward(step_names):
    """The custom-VJP backward of qconv inherits its scope: Q_E2's ops are
    named under `qconv` in the transposed pass."""
    hits = [n for n in step_names if holds(n, "q_e2")]
    assert hits and all(holds(n, "qconv") and "transpose(" in n
                        for n in hits)


_WIRE_PROG = textwrap.dedent("""
    import re
    import jax, jax.numpy as jnp, numpy as np
    from bench.layers import words
    from repro.configs.base import ArchConfig
    from repro.core import preset
    from repro.launch import shard as S
    from repro.launch.mesh import make_cpu_mesh
    from repro.launch.train import make_sharded_train_step
    from repro.models import build_model
    from repro.optim import init_momentum

    a = ArchConfig(name="t-rn", family="resnet", block="basic",
                   stage_sizes=(1,), num_classes=10, img_size=16)
    qcfg = preset("full8", "native")
    model = build_model(a, qcfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_cpu_mesh(2, 1)
    batch = {"images": jnp.zeros((4, 16, 16, 3), jnp.float32),
             "labels": jnp.zeros((4,), jnp.int32)}
    for codec, opt_shard in (("packed", "replicated"), ("leaf", "zero1")):
        fn, specs = make_sharded_train_step(
            model, qcfg, model.labels(params), mesh, params, n_shards=2,
            wire_codec=codec, opt_shard=opt_shard)
        p = S.shard_arrays(mesh, params, specs["params"])
        opt = (S.zero_init_momentum(params, 2) if opt_shard == "zero1"
               else init_momentum(params))
        o = S.shard_arrays(mesh, opt, specs["opt"])
        text = jax.jit(fn).lower(p, o, batch, jnp.int32(0)).compile() \\
            .as_text()
        names = re.findall(r'op_name="([^"]*)"', text)

        def holds(scope):
            return [n for n in names if scope in words(n)]

        wire, cq, opt = holds("wire"), holds("cq"), holds("momentum_update")
        print(codec, opt_shard, len(wire),
              sum("transpose(" in n for n in wire),
              len(set(cq) & set(opt)), len(set(cq)))
""")


def test_wire_scope_in_the_sharded_step():
    """The integer wire's encode, ring and decode run under `wire`, outside
    the backward pass; CQ runs under `momentum_update` in both optimizer
    layouts (2 virtual CPU devices)."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run([sys.executable, "-c", _WIRE_PROG],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=_ROOT)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    rows = [line.split() for line in r.stdout.splitlines()]
    assert [row[:2] for row in rows] == [["packed", "replicated"],
                                         ["leaf", "zero1"]]
    for _, _, n_wire, n_wire_bwd, n_cq_opt, n_cq in rows:
        assert int(n_wire) > 0 and int(n_wire_bwd) == 0
        assert int(n_cq) > 0 and int(n_cq_opt) == int(n_cq)


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


F32, I8, I32 = jnp.float32, jnp.int8, jnp.int32
SCALAR = _sds((), F32)
# (kernel, call in interpret mode, argument shapes)
KERNELS = [
    ("qmatmul", lambda a, b: qmatmul(a, b, interpret=True),
     [_sds((64, 128), I8), _sds((128, 128), I8)]),
    ("bwd_dgrad", lambda g, b, s: bwd_dgrad(g, b, s, mode="affine", k=8,
                                           interpret=True),
     [_sds((64, 128)), _sds((128, 128), I8), _sds((3,))]),
    ("bwd_wgrad", lambda a, g, s: bwd_wgrad(a, g, s, mode="affine", k=8,
                                           interpret=True),
     [_sds((64, 128), I8), _sds((64, 128)), _sds((3,))]),
    ("quantize_fused", lambda x, s: quantize_fused(x, s, interpret=True),
     [_sds((64, 128)), SCALAR]),
    ("cq_stochastic", lambda x, b, s: cq_stochastic(x, b, s, interpret=True),
     [_sds((64, 128)), _sds((64, 128), jnp.uint32), SCALAR]),
    ("ubn_norm", lambda x, g, b: ubn_norm(x, g, b, kind="batch",
                                         interpret=True),
     [_sds((64, 128)), _sds((128,)), _sds((128,))]),
    ("flash_attention",
     lambda q, k, v, qp, kp, kval, qs, ks, vs: flash_attention(
         q, k, v, qp, kp, kval, qs, ks, vs, causal=True, sm_scale=0.125,
         q_chunk=128, kv_chunk=128, interpret=True),
     [_sds((1, 128, 2, 64), I8)] * 3 + [_sds((128,), I32)] * 3
     + [SCALAR] * 3),
    ("paged_attention",
     lambda q, kp, vp, t, qp, tv, qs, ks, vs: paged_attention(
         q, kp, vp, t, qp, tv, qs, ks, vs, sm_scale=0.125, interpret=True),
     [_sds((2, 2, 64), I8), _sds((8, 16, 2, 64), I8),
      _sds((8, 16, 2, 64), I8), _sds((2, 4), I32), _sds((2,), I32),
      _sds((), I32), SCALAR, SCALAR, SCALAR]),
    ("page_gather", lambda p, t: page_gather(p, t, interpret=True),
     [_sds((8, 16, 128), I8), _sds((2, 4), I32)]),
    ("selective_scan", lambda a, b, c: selective_scan(a, b, c,
                                                      interpret=True),
     [_sds((1, 32, 128, 16)), _sds((1, 32, 128, 16)), _sds((1, 32, 16))]),
]


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(str(eqn.params["name"]))
            continue
        for v in eqn.params.values():
            for vv in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(vv, "eqns"):
                    _pallas_names(vv, out)
                elif hasattr(vv, "jaxpr") and hasattr(vv.jaxpr, "eqns"):
                    _pallas_names(vv.jaxpr, out)
    return out


@pytest.mark.parametrize("name,fn,args", KERNELS,
                         ids=[k[0] for k in KERNELS])
def test_kernel_carries_its_name(name, fn, args):
    """Every pallas_call is named after its kernel function, so the
    kernel's events in a trace do not depend on the jitted wrapper."""
    names = _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr, [])
    assert names and set(names) == {name}, names


def test_every_pallas_call_in_kernels_is_named():
    src = os.path.join(_ROOT, "src", "repro", "kernels")
    for fname in sorted(os.listdir(src)):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(src, fname)) as f:
            text = f.read()
        calls = text.split("pl.pallas_call(")[1:]
        for call in calls:
            # the call's keyword arguments end at the first ")(" — the
            # application of the built kernel to its operands
            assert re.search(r"\bname=", call.split(")(")[0]), fname
