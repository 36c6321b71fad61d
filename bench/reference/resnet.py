"""Plain reference of WAGEUBN full-integer ResNet training (arXiv 1909.02384).

Straightforward jax.numpy in float32, written from the paper's equations
and the configuration file alone: no kernels, no integer payloads, no
import of the system under test.  Every bit width comes from the
configuration's `quant` block, and the precision of the float32 first conv
and last FC from its `float32_precision`, so the same code computes the
controls of the correctness check when either is lowered.

Per step (paper Alg. 1-2, Eq. 6-24):
  forward   first conv and last FC in float32; every other conv sees
            Q_W(w) = clip(Q(w, k_w), +-(1 - 2^(1-k_w))) and activations on
            the Q_A grid (power-of-two amax scale, at least 1); every BN but
            the first quantizes mu, sigma, x_hat, gamma and beta directly;
  backward  straight-through quantizers; the error entering each ReLU/Q_A
            boundary is shift-quantized (Q_E1 = SQ at k_e1), the error
            entering each quantized conv is flag-quantized (Q_E2 at k_e2);
  optimizer conv weights take CQ gradients (stochastic rounding, dynamic
            range 2^(k_gdr-1), constant scale 2^(1-k_gc)), gamma/beta take
            Q(g, k_ggamma/k_gbeta), momentum accumulates on the k_acc grid
            and weights land on the k_wu grid; first/last layers keep plain
            float32 momentum.

`init_params` is the benchmark's own seeded initialisation; it builds the
parameter tree that the system under test takes (nested dicts and lists of
float32 leaves), so the program and this reference start from the same
weights without either making them for the other.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# `float32_precision` of the configuration -> the precision of the float32
# first conv and last FC ("highest": float32; "default": one bfloat16 pass
# on a TPU, the control).
FLOAT32 = {"highest": lax.Precision.HIGHEST,
           "default": lax.Precision.DEFAULT}

# ---------------------------------------------------------------- set-up


def _stage_channels(cfg):
    mult = 4 if cfg["block"] == "bottleneck" else 1
    return [w * mult for w in cfg["widths"]]


def _wgrid(q, w):
    """A weight draw put on the k_wu grid, the one the optimizer keeps."""
    k = q["wu"]
    lim = 1.0 - 2.0 ** (1 - k)
    return jnp.clip(jnp.round(w * 2.0 ** (k - 1)) / 2.0 ** (k - 1), -lim, lim)


def init_params(cfg, key):
    """Seeded weights: convs N(0, 1/fan_in) on the k_wu grid, BN gamma 1 and
    beta 0, stem N(0, 0.05^2), FC N(0, 0.01^2), FC bias 0."""
    q = cfg["quant"]
    bottleneck = cfg["block"] == "bottleneck"
    keys = iter(jax.random.split(key, 4 + 5 * sum(cfg["stage_sizes"])))

    def conv(kh, cin, cout):
        w = jax.random.normal(next(keys), (kh, kh, cin, cout), jnp.float32)
        return _wgrid(q, w / math.sqrt(kh * kh * cin))

    def bn(c):
        return {"gamma": jnp.ones((c,), jnp.float32),
                "beta": jnp.zeros((c,), jnp.float32)}

    chans = _stage_channels(cfg)
    stages, cin = [], 64
    for si, n in enumerate(cfg["stage_sizes"]):
        cout, blocks = chans[si], []
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            if bottleneck:
                mid = cout // 4
                p = {"conv1": conv(1, cin, mid), "bn1": bn(mid),
                     "conv2": conv(3, mid, mid), "bn2": bn(mid),
                     "conv3": conv(1, mid, cout), "bn3": bn(cout)}
            else:
                p = {"conv1": conv(3, cin, cout), "bn1": bn(cout),
                     "conv2": conv(3, cout, cout), "bn2": bn(cout)}
            if stride != 1 or cin != cout:
                p["proj"] = conv(1, cin, cout)
                p["bn_proj"] = bn(cout)
            blocks.append(p)
            cin = cout
        stages.append(blocks)
    return {
        "stem": jax.random.normal(next(keys), (7, 7, 3, 64)) * 0.05,
        "bn_stem": bn(64),
        "stages": stages,
        "fc": jax.random.normal(next(keys), (chans[-1], cfg["num_classes"]))
        * 0.01,
        "fc_b": jnp.zeros((cfg["num_classes"],), jnp.float32),
    }


def leaf_kinds(params):
    """Optimizer class of every leaf, by its place in the tree: "exempt"
    (stem, FC), "gamma"/"beta" (every BN), "w" (every other conv)."""
    def kind(path, _):
        names = [getattr(p, "key", None) for p in path]
        if names[0] in ("stem", "fc", "fc_b"):
            return "exempt"
        if names[-1] in ("gamma", "beta"):
            return names[-1]
        return "w"
    return jax.tree_util.tree_map_with_path(kind, params)


# ---------------------------------------------------------- quantizers


def q_direct(x, k):
    s = 2.0 ** (k - 1)
    return jnp.round(x * s) / s


def _pow2_round(m):
    safe = jnp.where(m > 0, m, 1.0)
    return jnp.where(m > 0, jnp.exp2(jnp.round(jnp.log2(safe))), 1.0)


def _pow2_ceil(m):
    safe = jnp.where(m > 0, m, 1.0)
    return jnp.where(m > 0, jnp.exp2(jnp.ceil(jnp.log2(safe))), 1.0)


def _amax(x):
    return jnp.max(jnp.abs(x))


def q_weight_fwd(w, k):
    lim = 1.0 - 2.0 ** (1 - k)
    return jnp.clip(q_direct(w, k), -lim, lim)


def q_act_fwd(x, k):
    s = jnp.maximum(_pow2_ceil(_amax(x)), 1.0)
    lim = 1.0 - 2.0 ** (1 - k)
    return s * jnp.clip(q_direct(x / s, k), -lim, lim)


def shift_q(x, k):
    r = _pow2_round(_amax(x))
    lim = 1.0 - 2.0 ** (1 - k)
    return r * jnp.clip(q_direct(x / r, k), -lim, lim)


def flag_q(x, k):
    r = _pow2_round(_amax(x))
    sc = r / 2.0 ** (k - 1)
    n = x / sc
    lim = 2.0 ** (k - 1) - 1.0
    big = sc * jnp.clip(jnp.round(n), -lim, lim)
    return jnp.where(jnp.abs(n) >= 1.0, big, sc * q_direct(n, k))


def const_q(g, key, dr_bits, k_gc):
    r = _pow2_round(_amax(g))
    dr = float(2 ** (dr_bits - 1))
    y = dr * (g / r)
    f = jnp.floor(y)
    y = f + (jax.random.uniform(key, g.shape) < (y - f)).astype(g.dtype)
    return jnp.clip(y, -dr + 1.0, dr - 1.0) / 2.0 ** (k_gc - 1)


def _ste(fn):
    @jax.custom_vjp
    def f(x):
        return fn(x)
    f.defvjp(lambda x: (fn(x), None), lambda _, g: (g,))
    return f


# ------------------------------------------------------------ layers


def _conv(x, w, stride, precision):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


# The quantized convs run at the default precision: every operand they see
# is on a grid of at most 8 significant bits (Q_W: n 2^-7; Q_A: s n 2^-7;
# Q_E2: an int8 mantissa in one of two power-of-two regimes), which
# bfloat16 holds exactly, so each product is exact and the sums are float32
# as at HIGHEST, in another order.
LOW = lax.Precision.DEFAULT


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def qconv(x, wq, stride, k_e2):
    return _conv(x, wq, stride, LOW)


def _qconv_fwd(x, wq, stride, k_e2):
    return _conv(x, wq, stride, LOW), (x, wq)


def _qconv_bwd(stride, k_e2, res, g):
    x, wq = res
    _, vjp = jax.vjp(lambda a, b: _conv(a, b, stride, LOW), x, wq)
    return vjp(flag_q(g, k_e2))


qconv.defvjp(_qconv_fwd, _qconv_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def qact(x, relu, k_a, k_e1):
    return q_act_fwd(jax.nn.relu(x) if relu else x, k_a)


def _qact_fwd(x, relu, k_a, k_e1):
    return qact(x, relu, k_a, k_e1), x


def _qact_bwd(relu, k_a, k_e1, x, g):
    e = shift_q(g, k_e1)
    return (e * (x > 0).astype(e.dtype) if relu else e,)


qact.defvjp(_qact_fwd, _qact_bwd)

EPS_Q = 2.0 ** -8      # epsilon_q of Eq. 12


def batchnorm(x, gamma, beta, q=None):
    """BN over every axis but channels; q=None is the float32 first layer."""
    axes = tuple(range(x.ndim - 1))
    mu = jnp.mean(x, axes)
    var = jnp.mean(jnp.square(x), axes) - jnp.square(mu)
    sigma = jnp.sqrt(jnp.maximum(var, 0.0))
    if q is None:
        return gamma * ((x - mu) / (sigma + EPS_Q)) + beta
    qd = lambda k: _ste(lambda t: q_direct(t, k))
    xhat = (x - qd(q["mu"])(mu)) / (qd(q["sigma"])(sigma) + EPS_Q)
    return qd(q["gamma"])(gamma) * qd(q["bn"])(xhat) + qd(q["beta"])(beta)


def _block(q, p, x, stride, bottleneck):
    wq = lambda w: _ste(lambda t: q_weight_fwd(t, q["w"]))(w)
    conv = lambda h, name, s=1: qconv(h, wq(p[name]), s, q["e2"])
    bn = lambda h, name: batchnorm(h, p[name]["gamma"], p[name]["beta"], q)
    act = lambda h: qact(h, True, q["a"], q["e1"])
    if bottleneck:
        h = act(bn(conv(x, "conv1"), "bn1"))
        h = act(bn(conv(h, "conv2", stride), "bn2"))
        h = bn(conv(h, "conv3"), "bn3")
    else:
        h = act(bn(conv(x, "conv1", stride), "bn1"))
        h = bn(conv(h, "conv2"), "bn2")
    idn = bn(conv(x, "proj", stride), "bn_proj") if "proj" in p else x
    return act(h + idn)


def forward(cfg, params, images):
    q = cfg["quant"]
    bottleneck = cfg["block"] == "bottleneck"
    f32 = FLOAT32[cfg["float32_precision"]]
    x = _conv(images, params["stem"], 2, f32)
    x = jax.nn.relu(batchnorm(x, params["bn_stem"]["gamma"],
                              params["bn_stem"]["beta"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    x = qact(x, False, q["a"], q["e1"])
    for si, blocks in enumerate(params["stages"]):
        for bi, p in enumerate(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            x = jax.checkpoint(partial(_block, q, stride=stride,
                                       bottleneck=bottleneck))(p, x)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, params["fc"], precision=f32) + params["fc_b"]


def loss_fn(cfg, params, batch):
    logits = forward(cfg, params, batch["images"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(lse - tgt)


# --------------------------------------------------------- optimizer


def fixed_point(v, k):
    """A hyper-parameter on the k-bit fixed-point grid (at least one step)."""
    s = 2.0 ** (k - 1)
    return max(round(v * s), 1.0) / s


def update(cfg, params, acc, grads, key):
    q, opt = cfg["quant"], cfg["optimizer"]
    lr = fixed_point(opt["lr"], q["lr"])
    mom_q = round(opt["mom"] * 2.0 ** (q["mom"] - 1)) / 2.0 ** (q["mom"] - 1)
    kinds = jax.tree.leaves(leaf_kinds(params))
    leaves, tree = jax.tree.flatten(params)
    gs, accs = tree.flatten_up_to(grads), tree.flatten_up_to(acc)
    keys = jax.random.split(key, len(leaves))
    lim = 1.0 - 2.0 ** (1 - q["wu"])
    new_p, new_a = [], []
    for p, g, a, kind, k in zip(leaves, gs, accs, kinds, keys):
        if kind == "exempt":
            a = opt["mom"] * a + g
            new_p.append(p - lr * a)
            new_a.append(a)
            continue
        if kind == "w":
            gq = const_q(g, k, q["gdr"], q["gc"])
        else:
            gq = q_direct(g, q["ggamma"] if kind == "gamma" else q["gbeta"])
        full = mom_q * q_direct(a, q["acc"]) + gq
        new_a.append(q_direct(full, q["acc"]))
        new_p.append(jnp.clip(q_direct(p - lr * full, q["wu"]), -lim, lim))
    return tree.unflatten(new_p), tree.unflatten(new_a)


def train_step(cfg, params, acc, batch, key, n_shards=1):
    """One reference step; with n_shards > 1 every shard of the batch runs
    forward and backward on its own (its own BN statistics and quantizer
    scales) and the gradients are averaged, as data-parallel workers do."""
    grad = jax.value_and_grad(partial(loss_fn, cfg))
    shards = jax.tree.map(
        lambda x: x.reshape((n_shards, -1) + x.shape[1:]), batch)
    loss, grads = 0.0, None
    for i in range(n_shards):
        l, g = grad(params, jax.tree.map(lambda x: x[i], shards))
        loss = loss + l / n_shards
        g = jax.tree.map(lambda t: t / n_shards, g)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    params, acc = update(cfg, params, acc, grads, key)
    return params, acc, loss, grads
