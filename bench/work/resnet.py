"""Work that ResNet training needs, counted from the architecture's shapes.

Independent of how the system under test implements it: the counts follow
the published network (He et al. 2016) and the WAGEUBN data paths, never the
compiled program, so a change of implementation leaves the yardstick alone.

`convs(cfg)` lists every convolution with its input size; the rest derive
from it:
  * `fwd_macs`: multiply-adds of one image's forward convs and FC;
  * `model_flops_per_sample(cfg, traffic)`: 3 x 2 x fwd_macs (forward,
    input gradient, weight gradient), the usual training convention;
  * `conv_work(cfg, traffic, batch)`: FLOPs and least bytes of the
    convolutions a step has to run (forward of all; weight gradient of all;
    input gradient of all but the stem, whose input is the image), with
    int8 operands and float32 results;
  * `quantized_elements(cfg, traffic, batch)`: elements a step puts on an
    int8 grid through Q_W (every conv weight but the stem's) and Q_A (every
    activation quantizer: after the stem's max-pool and after each ReLU).
A sample is one image at the configuration's size, so `traffic` (the
signatures of `work/__init__.py`) changes no count here.
"""
from __future__ import annotations


def _out(h, stride):
    return -(-h // stride)          # SAME padding


def convs(cfg):
    """[(name, h_in, w_in, c_in, c_out, k, stride), ...] for one image."""
    bottleneck = cfg["block"] == "bottleneck"
    mult = 4 if bottleneck else 1
    h = cfg["img_size"]
    out = [("stem", h, h, 3, 64, 7, 2)]
    h = _out(_out(h, 2), 2)               # stem stride 2, max-pool stride 2
    cin = 64
    for si, n in enumerate(cfg["stage_sizes"]):
        cout = cfg["widths"][si] * mult
        for bi in range(n):
            s = 2 if (si > 0 and bi == 0) else 1
            tag = f"s{si}b{bi}"
            if bottleneck:
                mid = cout // 4
                out += [(f"{tag}.conv1", h, h, cin, mid, 1, 1),
                        (f"{tag}.conv2", h, h, mid, mid, 3, s),
                        (f"{tag}.conv3", _out(h, s), _out(h, s), mid, cout,
                         1, 1)]
            else:
                out += [(f"{tag}.conv1", h, h, cin, cout, 3, s),
                        (f"{tag}.conv2", _out(h, s), _out(h, s), cout, cout,
                         3, 1)]
            if s != 1 or cin != cout:
                out.append((f"{tag}.proj", h, h, cin, cout, 1, s))
            h, cin = _out(h, s), cout
    return out


def _conv_shape(c):
    """(input elements, output elements, weights, MACs) of one image."""
    _, h, w, cin, cout, k, s = c
    out = _out(h, s) * _out(w, s) * cout
    return h * w * cin, out, k * k * cin * cout, out * k * k * cin


def fc_macs(cfg):
    mult = 4 if cfg["block"] == "bottleneck" else 1
    return cfg["widths"][-1] * mult * cfg["num_classes"]


def fwd_macs(cfg):
    return sum(_conv_shape(c)[3] for c in convs(cfg)) + fc_macs(cfg)


def conv_output_elements(cfg):
    return sum(_conv_shape(c)[1] for c in convs(cfg))


def param_count(cfg):
    bn = 2 * sum(c[4] for c in convs(cfg))     # every conv is followed by BN
    fc = fc_macs(cfg) + cfg["num_classes"]
    return sum(_conv_shape(c)[2] for c in convs(cfg)) + bn + fc


def model_flops_per_sample(cfg, traffic):
    return 6 * fwd_macs(cfg)


def conv_work(cfg, traffic, batch):
    """(flops, least bytes) of one step's convolutions: int8 operands,
    float32 results, each pass reading its operands and writing its result
    once."""
    flops = nbytes = 0
    for c in convs(cfg):
        x, y, w, macs = _conv_shape(c)
        passes = [(batch * x, w, batch * y)]          # forward
        passes.append((batch * x, batch * y, w))      # weight gradient
        if c[0] != "stem":
            passes.append((batch * y, w, batch * x))  # input gradient
        for a, b, res in passes:
            flops += 2 * batch * macs
            nbytes += a + b + 4 * res
    return flops, nbytes


def quantized_elements(cfg, traffic, batch=None):
    """Elements one step quantizes to int8 through Q_W and Q_A.

    `quantized_elements(cfg, batch)`, with no traffic, is the form that
    `tests/test_kernels.py` calls: an image's count takes none."""
    if batch is None:
        batch = traffic
    weights = sum(_conv_shape(c)[2] for c in convs(cfg) if c[0] != "stem")
    h = _out(_out(cfg["img_size"], 2), 2)
    acts = h * h * 64                                  # after the max-pool
    # one ReLU per conv of a block's main path: the last one is the
    # residual ReLU, of the same shape; projections feed that same ReLU
    acts += sum(_conv_shape(c)[1] for c in convs(cfg)[1:]
                if not c[0].endswith("proj"))
    return weights + batch * acts
