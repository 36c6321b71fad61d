"""work/resnet.py against the program's own shapes, counted on the CPU, and
the architecture a configuration file gives the program."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, spec
from bench.work import resnet as W

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _conv_eqns(jaxpr):
    """Every convolution of a jaxpr, nested jaxprs (custom VJPs, jits)
    included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            yield eqn
            continue
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _conv_eqns(inner)


@pytest.mark.parametrize("name", ["tiny18", "tiny50"])
def test_counts_match_the_programs_model(name):
    from repro.core.qconfig import preset
    from repro.models import build_model

    with open(os.path.join(DATA, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    model = build_model(program.arch_config(cfg), preset("fp32"))
    params = model.init(jax.random.PRNGKey(0))
    assert W.param_count(cfg) == sum(x.size for x in jax.tree.leaves(params))
    s = cfg["img_size"]
    images = jnp.zeros((1, s, s, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(model.forward)(params, images).jaxpr
    macs = outs = 0
    for eqn in _conv_eqns(jaxpr):
        w = eqn.invars[1].aval.shape
        y = eqn.outvars[0].aval.shape
        outs += int(np.prod(y))
        macs += int(np.prod(y)) * w[0] * w[1] * w[2]
    assert outs == W.conv_output_elements(cfg)
    assert macs + W.fc_macs(cfg) == W.fwd_macs(cfg)


def test_published_sizes():
    r50 = spec.load("configs", "resnet50")
    r18 = spec.load("configs", "resnet18")
    assert W.param_count(r50) == 25_557_032
    assert W.param_count(r18) == 11_689_512
    assert W.fwd_macs(r50) == 4_089_184_256
    assert W.fwd_macs(r18) == 1_814_073_344


def test_quantized_elements_match_the_cpu_trace_count():
    # 101 int8 quantize calls over 311,656,448 elements at batch 32,
    # counted from a CPU trace of the program's ResNet-50 step
    r50 = spec.load("configs", "resnet50")
    assert W.quantized_elements(r50, spec.load("traffic", "b64"), 32) \
        == 311_656_448


# The counts the benchmark's ResNet cells read, as the work functions gave
# them before they took the traffic: (config, traffic, model FLOPs per
# sample, conv_work at the traffic's batch, quantized_elements at it).
CELL_COUNTS = [
    ("resnet50", "b64", 24_535_105_536, (1_554_354_536_448, 8_412_936_128),
     599_867_392),
    ("resnet18", "b8-online", 10_884_440_064, (85_162_721_280, 279_738_304),
     24_805_376),
]


@pytest.mark.parametrize("name,traffic,flops,conv,quantized", CELL_COUNTS,
                         ids=[c[0] for c in CELL_COUNTS])
def test_cell_counts_unchanged(name, traffic, flops, conv, quantized):
    cfg, tr = spec.load("configs", name), spec.load("traffic", traffic)
    assert W.model_flops_per_sample(cfg, tr) == flops
    assert W.conv_work(cfg, tr, tr["batch"]) == conv
    assert W.quantized_elements(cfg, tr, tr["batch"]) == quantized


@pytest.mark.parametrize("name", ["resnet50", "resnet18"])
def test_arch_config_is_the_four_key_replace(name):
    """For the ResNet files the helper replaces exactly the four fields the
    program was given before it read every field the file names."""
    from repro.configs import get as get_arch

    cfg = spec.load("configs", name)
    want = get_arch(cfg["arch"]).replace(
        block=cfg["block"], stage_sizes=tuple(cfg["stage_sizes"]),
        num_classes=cfg["num_classes"], img_size=cfg["img_size"])
    got = program.arch_config(cfg)
    assert got == want
    assert got.skip_notes == want.skip_notes      # not compared by ==


def test_arch_config_takes_every_field_the_file_names():
    cfg = spec.load("configs", "tinylm", DATA)
    a = program.arch_config(cfg)
    assert (a.name, a.family, a.n_layers, a.d_model, a.n_heads, a.n_kv,
            a.head_dim, a.d_ff, a.vocab) == ("granite-3-8b", "lm", 2, 64, 4,
                                             2, 16, 96, 128)
    a = program.arch_config(dict(cfg, stage_sizes=[1, [2, 3]]))
    assert a.stage_sizes == (1, (2, 3))


def test_arch_config_refuses_another_family():
    cfg = dict(spec.load("configs", "tinylm", DATA), family="resnet")
    with pytest.raises(SystemExit, match="family 'resnet'"):
        program.arch_config(cfg)
