"""work/resnet.py against the program's own shapes, counted on the CPU."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
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
    from repro.configs import get as get_arch
    from repro.core.qconfig import preset
    from repro.models import build_model

    with open(os.path.join(DATA, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    acfg = get_arch(cfg["arch"]).replace(
        block=cfg["block"], stage_sizes=tuple(cfg["stage_sizes"]),
        num_classes=cfg["num_classes"], img_size=cfg["img_size"])
    model = build_model(acfg, preset("fp32"))
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
    assert W.quantized_elements(spec.load("configs", "resnet50"), 32) \
        == 311_656_448
