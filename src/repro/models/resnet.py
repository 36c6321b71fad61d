"""Paper-faithful ResNet18/34/50 with WAGEUBN quantized conv + BN + Momentum.

First conv and final FC are exempt from quantization (paper §IV-A).  Every
hidden conv goes through qconv (Q_W weights, Q_E2 errors), every BN through
qbatchnorm (Eq. 12), every ReLU through qact (Q_A forward / Q_E1 backward).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import qact, qconv, qbatchnorm, qt_carrier, qweight
from repro.core.qconfig import QConfig
from repro.configs.base import ArchConfig
from . import layers as L

Array = jax.Array


def _conv_init(cfg, key, kh, kw, cin, cout):
    return L.winit(cfg, key, (kh, kw, cin, cout), kh * kw * cin)


def _bn_init(c):
    return {"gamma": jnp.ones((c,), jnp.float32),
            "beta": jnp.zeros((c,), jnp.float32)}


class ResNet:
    def __init__(self, acfg: ArchConfig, qcfg: QConfig, mesh=None,
                 dp_axes=("data",), tp_axis="model", tp_size: int = 1):
        self.a, self.q = acfg, qcfg
        self.mesh, self.dp, self.tp = mesh, dp_axes, tp_axis
        self.tp_size = tp_size
        if tp_size != 1:
            raise ValueError(
                f"{type(self).__name__} supports DP-only sharding "
                f"(manual TP shards attention heads / FFN / experts; "
                f"got tp_size={tp_size})")
        self.bottleneck = acfg.block == "bottleneck"
        self.widths = (64, 128, 256, 512)[: len(acfg.stage_sizes)]

    def _init_block(self, key, cin, cout, stride):
        ks = jax.random.split(key, 5)
        if self.bottleneck:
            mid = cout // 4
            p = {
                "conv1": _conv_init(self.q, ks[0], 1, 1, cin, mid),
                "bn1": _bn_init(mid),
                "conv2": _conv_init(self.q, ks[1], 3, 3, mid, mid),
                "bn2": _bn_init(mid),
                "conv3": _conv_init(self.q, ks[2], 1, 1, mid, cout),
                "bn3": _bn_init(cout),
            }
        else:
            p = {
                "conv1": _conv_init(self.q, ks[0], 3, 3, cin, cout),
                "bn1": _bn_init(cout),
                "conv2": _conv_init(self.q, ks[1], 3, 3, cout, cout),
                "bn2": _bn_init(cout),
            }
        if stride != 1 or cin != cout:
            p["proj"] = _conv_init(self.q, ks[3], 1, 1, cin, cout)
            p["bn_proj"] = _bn_init(cout)
        return p

    def init(self, key):
        a = self.a
        ks = jax.random.split(key, 3 + len(a.stage_sizes))
        mult = 4 if self.bottleneck else 1
        params = {
            # first layer exempt (fp32)
            "stem": jax.random.normal(ks[0], (7, 7, 3, 64)) * 0.05,
            "bn_stem": _bn_init(64),
            "stages": [],
            "fc": jax.random.normal(ks[1], (self.widths[-1] * mult,
                                            a.num_classes)) * 0.01,
            "fc_b": jnp.zeros((a.num_classes,), jnp.float32),
        }
        cin = 64
        stages = []
        for si, n in enumerate(a.stage_sizes):
            cout = self.widths[si] * mult
            blocks = []
            bks = jax.random.split(ks[2 + si], n)
            for bi in range(n):
                stride = 2 if (si > 0 and bi == 0) else 1
                blocks.append(self._init_block(bks[bi], cin, cout, stride))
                cin = cout
            stages.append(blocks)
        params["stages"] = stages
        return params

    def labels(self, params):
        def bn_lab(_):
            return {"gamma": "gamma", "beta": "beta"}
        lab = {"stem": "exempt", "bn_stem": bn_lab(None), "stages": [],
               "fc": "exempt", "fc_b": "exempt"}
        for blocks in params["stages"]:
            st = []
            for b in blocks:
                d = {}
                for k in b:
                    d[k] = bn_lab(None) if k.startswith("bn") else "w"
                st.append(d)
            lab["stages"].append(st)
        return lab

    def pspecs(self):
        return jax.tree.map(lambda _: P(), {})  # CPU-scale model

    def _block(self, p, x, stride):
        q = self.q
        idn = x
        if self.bottleneck:
            h = qact(q, "relu", qbatchnorm(q, qconv(
                q, x, qweight(q, p["conv1"]), 1, "SAME"),
                p["bn1"]["gamma"], p["bn1"]["beta"]))
            h = qact(q, "relu", qbatchnorm(q, qconv(
                q, h, qweight(q, p["conv2"]), stride, "SAME"),
                p["bn2"]["gamma"], p["bn2"]["beta"]))
            h = qbatchnorm(q, qconv(q, h, qweight(q, p["conv3"]), 1, "SAME"),
                           p["bn3"]["gamma"], p["bn3"]["beta"])
        else:
            h = qact(q, "relu", qbatchnorm(q, qconv(
                q, x, qweight(q, p["conv1"]), stride, "SAME"),
                p["bn1"]["gamma"], p["bn1"]["beta"]))
            h = qbatchnorm(q, qconv(q, h, qweight(q, p["conv2"]), 1, "SAME"),
                           p["bn2"]["gamma"], p["bn2"]["beta"])
        if "proj" in p:
            idn = qbatchnorm(q, qconv(q, x, qweight(q, p["proj"]), stride,
                                      "SAME"),
                             p["bn_proj"]["gamma"], p["bn_proj"]["beta"])
        return qact(q, "relu", h + idn)

    def forward(self, params, images):
        q = self.q
        with jax.named_scope("stem"):
            # exempt stem (fp32 conv + BN + relu, no quantizers)
            x = jax.lax.conv_general_dilated(
                images, params["stem"], (2, 2), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            from repro.core.qconfig import FP32
            x = qbatchnorm(FP32, x, params["bn_stem"]["gamma"],
                           params["bn_stem"]["beta"])
            x = jax.nn.relu(x)
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                      (1, 2, 2, 1), "SAME")
            x = qact(q, "none", x)
        for si, blocks in enumerate(params["stages"]):
            for bi, bp in enumerate(blocks):
                stride = 2 if (si > 0 and bi == 0) else 1
                with jax.named_scope(f"stage{si}"), \
                        jax.named_scope(f"block{bi}"):
                    x = self._block(bp, x, stride)
        with jax.named_scope("head"):
            x = jnp.mean(qt_carrier(x), axis=(1, 2))
            return x @ params["fc"] + params["fc_b"]  # exempt last layer

    def loss(self, params, batch, key=None):
        logits = self.forward(params, batch["images"])
        labels = batch["labels"]
        with jax.named_scope("head"):
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
            loss = jnp.mean(lse - tgt)
            acc = jnp.mean((jnp.argmax(logits, -1) == labels)
                           .astype(jnp.float32))
        return loss, {"loss": loss, "acc": acc}

    def input_specs(self, shape_name=None):
        a = self.a
        return {
            "images": jax.ShapeDtypeStruct((128, a.img_size, a.img_size, 3),
                                           jnp.float32),
            "labels": jax.ShapeDtypeStruct((128,), jnp.int32),
        }, "train"


RESNET_STAGES = {
    "resnet18": ("basic", (2, 2, 2, 2)),
    "resnet34": ("basic", (3, 4, 6, 3)),
    "resnet50": ("bottleneck", (3, 4, 6, 3)),
}
