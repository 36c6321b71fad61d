"""First-class quantized tensors + the pluggable quantizer registry.

This module is the WAGEUBN data model (DESIGN.md §2): every low-bit path
(W/A/G/E/U/BN) carries an integer payload with a power-of-two scale, and a
`QTensor` makes that payload the object that flows through the program
instead of being re-derived from fp32 grid carriers at every matmul.

  QTensor    — pytree of (integer data, pow2 scale[, low plane][, carrier]).
               `data * scale (+ lo * lo_scale)` is the represented value;
               `carrier`, when present, is the same value as a differentiable
               fp32 leaf so autodiff routes around the integer payload.
  Quantizer  — protocol: `__call__` (grid fp32 output, the legacy/sim
               semantics), `quantize` (-> QTensor, decompose exactly once),
               `dequantize`, `planes` (multi-plane formats like flag8).
  registry   — `register_quantizer` / `get_quantizer` / `resolve_quantizer`;
               legacy string kinds ("flag8", "sq16", "dec_int8", ...) resolve
               through `ALIASES`, so old call sites keep working while new
               quantizers plug in without touching core dispatch.
  QuantSpec  — hashable (kind, k, params) triple used by QConfig's structured
               per-path quantizer fields.

Invariant validated by tests/test_qtensor.py: for every registered quantizer
`dequantize(quantize(x)) == __call__(x)` bit-exactly on in-range inputs, and
`__call__` delegates to the legacy qfuncs formula verbatim.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import quantize_op

from . import qfuncs as qf

Array = jax.Array

_FIELDS = ("data", "scale", "lo", "lo_scale", "carrier")


def payload_dtype(k: int):
    if k <= 8:
        return jnp.int8
    if k <= 16:
        return jnp.int16
    return jnp.int32


def _float0_like(x):
    return np.zeros(np.shape(x), jax.dtypes.float0)


@jax.tree_util.register_pytree_with_keys_class
@dataclass(frozen=True)
class QTensor:
    """Integer payload + power-of-two scale, registered as a jax pytree.

    value = data * scale (+ lo * lo_scale for two-plane formats).  `k` is the
    logical bit-width (static aux data, preserved through jit/grad/scan).
    `carrier` is an optional differentiable fp32 view of the same value:
    QTensors produced inside autodiff (qact / quantize_ste) carry one so
    cotangents have somewhere to flow; raw payloads (KV cache, wire formats)
    leave it None and are non-differentiable by construction.
    """

    data: Array
    scale: Array
    k: int = 8
    lo: Array | None = None
    lo_scale: Array | None = None
    carrier: Array | None = None

    # ---- pytree protocol -------------------------------------------------

    def tree_flatten_with_keys(self):
        children = [(jax.tree_util.GetAttrKey(n), getattr(self, n))
                    for n in _FIELDS]
        return children, self.k

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux, *children[2:])

    # ---- value semantics -------------------------------------------------

    def dequantize(self) -> Array:
        y = self.data.astype(jnp.float32) * self.scale
        if self.lo is not None:
            y = y + self.lo.astype(jnp.float32) * self.lo_scale
        return y

    def to_array(self) -> Array:
        """Differentiable fp32 view when available, else dequantize."""
        if self.carrier is not None:
            return self.carrier
        return self.dequantize()

    def __jax_array__(self) -> Array:
        return self.to_array()

    def planes(self):
        """((data, scale), ...) integer planes for native matmuls."""
        if self.lo is None:
            return ((self.data, self.scale),)
        return ((self.data, self.scale), (self.lo, self.lo_scale))

    def with_carrier(self) -> "QTensor":
        return dataclasses.replace(self, carrier=self.dequantize())

    def drop_carrier(self) -> "QTensor":
        """Payload-only view: what backward residuals store (4x memory win)."""
        if self.carrier is None:
            return self
        return dataclasses.replace(self, carrier=None)

    def requantize(self, step, k: int | None = None) -> Array:
        """Re-express the payload on a new pow2 step WITHOUT an amax pass.

        Returns the raw integer payload saturated to the TARGET width `k`
        (default: this tensor's own width) — a rounding shift plus clip,
        never a data-dependent rescan.  Pass k=8 when writing into an int8
        store (e.g. the KV cache) so wider payloads saturate instead of
        wrapping on the dtype cast.
        """
        k = self.k if k is None else k
        v = self.data.astype(jnp.float32) * (self.scale / step)
        if self.lo is not None:
            v = v + self.lo.astype(jnp.float32) * (self.lo_scale / step)
        lim = 2.0 ** (k - 1) - 1.0
        return jnp.clip(jnp.round(v), -lim, lim).astype(payload_dtype(k))

    # ---- array-like surface ---------------------------------------------

    @property
    def shape(self):
        return self.data.shape

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def size(self):
        return self.data.size

    @property
    def dtype(self):
        # logical dtype of the represented value (what dequantize returns)
        return jnp.dtype(jnp.float32)

    def _map_payload(self, fn) -> "QTensor":
        """Apply a shape-only op to every payload plane (scale unchanged)."""
        return dataclasses.replace(
            self, data=fn(self.data),
            lo=None if self.lo is None else fn(self.lo),
            carrier=None if self.carrier is None else fn(self.carrier))

    def reshape(self, *shape) -> "QTensor":
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return self._map_payload(lambda t: t.reshape(shape))

    def transpose(self, *axes) -> "QTensor":
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return self._map_payload(lambda t: t.transpose(axes or None))

    def swapaxes(self, a, b) -> "QTensor":
        return self._map_payload(lambda t: jnp.swapaxes(t, a, b))

    def __getitem__(self, idx) -> "QTensor":
        return self._map_payload(lambda t: t[idx])

    # arithmetic degrades to the fp32 view (differentiable via carrier)
    def __mul__(self, o):
        return self.to_array() * _arr(o)

    __rmul__ = __mul__

    def __add__(self, o):
        return self.to_array() + _arr(o)

    __radd__ = __add__

    def __sub__(self, o):
        return self.to_array() - _arr(o)

    def __rsub__(self, o):
        return _arr(o) - self.to_array()

    def __truediv__(self, o):
        return self.to_array() / _arr(o)

    def __rtruediv__(self, o):
        return _arr(o) / self.to_array()

    def __neg__(self):
        return -self.to_array()


def _arr(x) -> Array:
    """fp32 view of Array | QTensor (differentiable when carrier present)."""
    return x.to_array() if isinstance(x, QTensor) else x


# re-exported under a readable name for model code
qt_carrier = _arr


def qtensor_cotangent(like: QTensor, d_carrier) -> QTensor:
    """Cotangent pytree matching `like`'s structure.

    Integer payload leaves take float0 (non-differentiable), the scale takes
    a zero, and the fp32 gradient lands on the carrier leaf (None if `like`
    has no carrier — such QTensors are non-differentiable inputs).
    """
    return QTensor(
        _float0_like(like.data), jnp.zeros_like(like.scale), like.k,
        None if like.lo is None else _float0_like(like.lo),
        None if like.lo_scale is None else jnp.zeros_like(like.lo_scale),
        None if like.carrier is None else d_carrier)


# ==========================================================================
# Quantizer protocol + implementations
# ==========================================================================


def _decompose(x: Array, step, k: int) -> QTensor:
    """Shared payload decomposition: clip(round(x/step)) saturated to the
    signed k-bit range.  int8-width payloads route through the fused Pallas
    quantize kernel (kernels/ops.quantize_op — TPU kernel on x's own shape,
    jnp oracle on CPU); wider payloads lower through XLA.  `step` must be a
    power of two, so the reciprocal multiply is exact."""
    lim = 2.0 ** (k - 1) - 1.0
    step = jnp.asarray(step, jnp.float32)
    if k <= 8:
        data = quantize_op(x, jnp.float32(1.0) / step, lim=lim)
    else:
        data = jnp.clip(jnp.round(x / step), -lim,
                        lim).astype(payload_dtype(k))
    return QTensor(data, step, k)


@dataclass(frozen=True)
class Quantizer:
    """Base quantizer: `__call__` = legacy grid-carrier output (sim mode);
    `quantize` = native decomposition into a QTensor (exactly once);
    `dequantize(quantize(x)) == __call__(x)` bit-exactly on in-range inputs.

    Frozen dataclass => hashable => usable as a static custom_vjp argument.
    """

    k: int = 8

    name = "base"

    def __call__(self, x: Array, *, key: Array | None = None) -> Array:
        return self.dequantize(self.quantize(x, key=key))

    def quantize(self, x: Array, *, key: Array | None = None) -> QTensor:
        raise NotImplementedError

    def dequantize(self, qt: QTensor) -> Array:
        return qt.dequantize()

    def planes(self, qt: QTensor):
        return qt.planes()

    def fused_plan(self, x: Array):
        """Scalar recipe for fusing this quantizer into a matmul prologue.

        Returns (mode, plane_steps, k) — mode "affine" (one plane,
        payload = clip(round(x / plane_steps[0]), ±(2^(k-1)-1))) or "flag"
        (two planes at steps (Sc, Sc*2^(1-k))) — or None when the format
        cannot be fused (e.g. stochastic rounding needs a PRNG plane).
        Only the scale reduction (at most one amax) runs here; payload
        emission happens inside the fused kernel.  The planes must be
        bit-identical to `quantize(x).planes()`.
        """
        return None


@dataclass(frozen=True)
class IdentityQuantizer(Quantizer):
    """No forward quantization; native payloads use a lossless-on-grid 16-bit
    decomposition (the legacy `dec_int16` fallback for e_kind == "none")."""

    k: int = 16

    name = "none"

    def __call__(self, x, *, key=None):
        return x

    def quantize(self, x, *, key=None):
        s = jnp.maximum(qf.pow2_ceil(qf.amax(x)), 2.0 ** -24)
        return _decompose(x, s * 2.0 ** (1 - self.k), self.k)

    def fused_plan(self, x):
        s = jnp.maximum(qf.pow2_ceil(qf.amax(x)), 2.0 ** -24)
        return ("affine", (s * 2.0 ** (1 - self.k),), self.k)


@dataclass(frozen=True)
class GridQuantizer(IdentityQuantizer):
    """Decompose a tensor already on a fixed-point grid (paper "grid
    carriers", DESIGN.md §3): pow2_ceil(amax) scale, floor 2^-24.  This is
    the legacy `dec_int8`/`dec_int16` pair; lossless whenever x came from
    q_scaled / q_clip / sq at width <= k."""

    k: int = 8

    name = "grid"

    def __call__(self, x, *, key=None):
        return self.dequantize(self.quantize(x))


@dataclass(frozen=True)
class DirectQuantizer(Quantizer):
    """Q(x,k) = round(x * 2^(k-1)) / 2^(k-1)  (paper Eq. 6).  The payload
    decomposition clips to the signed k-bit range, so quantize/dequantize is
    exact only for |x| <= 1 - 2^(1-k) (the grid's representable range)."""

    name = "direct"

    def __call__(self, x, *, key=None):
        return qf.q_direct(x, self.k)

    def quantize(self, x, *, key=None):
        return _decompose(x, 2.0 ** (1 - self.k), self.k)

    def fused_plan(self, x):
        # fixed grid step: no amax at all
        return ("affine", (jnp.float32(2.0 ** (1 - self.k)),), self.k)


@dataclass(frozen=True)
class ClipQuantizer(Quantizer):
    """Q_W (paper Eq. 10): direct quantization saturating to (-1, 1).  The
    payload scale is the FIXED 2^(1-k) grid step — no amax pass, no scalar
    collective; the int8 copy is what FSDP gathers (legacy dec_int8_fixed)."""

    name = "clip"

    def __call__(self, x, *, key=None):
        return qf.q_clip(x, self.k)

    def quantize(self, x, *, key=None):
        return _decompose(x, 2.0 ** (1 - self.k), self.k)

    def fused_plan(self, x):
        return ("affine", (jnp.float32(2.0 ** (1 - self.k)),), self.k)


@dataclass(frozen=True)
class ScaledQuantizer(Quantizer):
    """Q_A (paper Eq. 14 + WAGE layer-wise pow2 scaling): amax pow2_ceil
    scale >= 1 extends coverage beyond (-1, 1); payload is int8-packable by
    construction (|n| <= 2^(k-1) - 1)."""

    name = "scaled"

    def __call__(self, x, *, key=None):
        return qf.q_scaled(x, self.k)

    def quantize(self, x, *, key=None):
        s = jnp.maximum(qf.pow2_ceil(qf.amax(x)), 1.0)
        return _decompose(x, s * 2.0 ** (1 - self.k), self.k)

    def fused_plan(self, x):
        s = jnp.maximum(qf.pow2_ceil(qf.amax(x)), 1.0)
        return ("affine", (s * 2.0 ** (1 - self.k),), self.k)


@dataclass(frozen=True)
class ShiftQuantizer(Quantizer):
    """SQ (paper Eq. 8): layer-wise pow2 scale R(x) = 2^round(log2 amax)."""

    name = "sq"

    def __call__(self, x, *, key=None):
        return qf.sq(x, self.k)

    def quantize(self, x, *, key=None):
        r = qf.pow2_round(qf.amax(x))
        return _decompose(x, r * 2.0 ** (1 - self.k), self.k)

    def fused_plan(self, x):
        r = qf.pow2_round(qf.amax(x))
        return ("affine", (r * 2.0 ** (1 - self.k),), self.k)


@dataclass(frozen=True)
class FlagQuantizer(Quantizer):
    """Flag-bit error quantization (paper Eq. 17 / Fig. 4): one int8 mantissa
    under two pow2 regimes.  `quantize` emits TWO disjoint-support int8
    planes (hi: multiples of Sc; lo: multiples of Sc*2^(1-k)) — the TPU
    realization of the 9-bit flag format where storage and both backward
    dots stay int8.  Sum of dequantized planes == flag_qe2(x) bit-exactly
    (the regime split keys off the rounded payload, so boundary values land
    where the legacy scalar formula puts them)."""

    name = "flag"

    def __call__(self, x, *, key=None):
        return qf.flag_qe2(x, self.k)

    def quantize(self, x, *, key=None):
        k = self.k
        r = qf.pow2_round(qf.amax(x))
        sc = r / 2.0 ** (k - 1)
        n = x / sc
        lim = 2.0 ** (k - 1) - 1.0
        nlo = jnp.round(n * 2.0 ** (k - 1))
        # |nlo| >= 2^(k-1) collapses to the hi regime (same value there)
        isbig = (jnp.abs(n) >= 1.0) | (jnp.abs(nlo) >= 2.0 ** (k - 1))
        hi = jnp.where(isbig, jnp.clip(jnp.round(n), -lim, lim), 0.0)
        lo = jnp.where(isbig, 0.0, jnp.clip(nlo, -lim, lim))
        dt = payload_dtype(k)
        return QTensor(hi.astype(dt), sc, k,
                       lo=lo.astype(dt), lo_scale=sc * 2.0 ** (1 - k))

    def fused_plan(self, x):
        r = qf.pow2_round(qf.amax(x))
        sc = r / 2.0 ** (self.k - 1)
        return ("flag", (sc, sc * 2.0 ** (1 - self.k)), self.k)


@dataclass(frozen=True)
class ConstantQuantizer(Quantizer):
    """CQ (paper Eq. 7) for weight gradients: range-normalized, constant
    pow2 scale 2^(1-k_gc), stochastic rounding, shrinking dr schedule."""

    k: int = 15          # k_gc: constant scale bits
    dr_bits: int = 8     # dr = 2^(dr_bits-1), shrinks during training
    stochastic: bool = True

    name = "cq"

    def __call__(self, x, *, key=None):
        return qf.cq(x, key, self.dr_bits, self.k, stochastic=self.stochastic)

    def quantize(self, x, *, key=None):
        r = qf.pow2_round(qf.amax(x))
        dr = float(2 ** (self.dr_bits - 1))
        y = dr * (x / r)
        if self.stochastic:
            assert key is not None, "stochastic CQ needs a PRNG key"
            y = qf.stochastic_round(y, key)
        else:
            y = jnp.round(y)
        data = jnp.clip(y, -dr + 1.0,
                        dr - 1.0).astype(payload_dtype(self.dr_bits))
        return QTensor(data, jnp.float32(2.0 ** (1 - self.k)), self.k)


# ==========================================================================
# registry
# ==========================================================================


_REGISTRY: dict[str, type] = {}

# legacy string kinds -> (registered name, fixed k or None)
ALIASES: dict[str, tuple[str, int | None]] = {
    "flag8": ("flag", 8),
    "sq8": ("sq", 8),
    "sq16": ("sq", 16),
    "q_direct": ("direct", None),
    "q_clip": ("clip", None),
    "q_scaled": ("scaled", None),
    "dec_int8": ("grid", 8),
    "dec_int16": ("grid", 16),
    "dec_int8_fixed": ("clip", 8),
    "identity": ("none", None),
}


def register_quantizer(name: str, cls: type) -> type:
    """Register a Quantizer class under `name`.  New quantizer kinds plug in
    here without touching core dispatch; returns cls for decorator use.
    Overriding an existing name takes effect immediately (the instance
    cache is invalidated)."""
    _REGISTRY[name] = cls
    get_quantizer.cache_clear()
    return cls


def registered_quantizers() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


@lru_cache(maxsize=None)
def get_quantizer(kind: str, k: int | None = None,
                  params: tuple = ()) -> Quantizer:
    """Instantiate (and cache) a quantizer by registry name or legacy alias.

    `params` is a tuple of (key, value) pairs so the lookup stays hashable.
    """
    if kind in ALIASES:
        name, fixed_k = ALIASES[kind]
        return get_quantizer(name, fixed_k if fixed_k is not None else k,
                             params)
    if kind not in _REGISTRY:
        raise ValueError(
            f"unknown quantizer {kind!r}; registered: {registered_quantizers()}")
    cls = _REGISTRY[kind]
    kw = dict(params)
    if k is not None:
        kw["k"] = k
    return cls(**kw)


for _cls in (IdentityQuantizer, GridQuantizer, DirectQuantizer,
             ClipQuantizer, ScaledQuantizer, ShiftQuantizer, FlagQuantizer,
             ConstantQuantizer):
    register_quantizer(_cls.name, _cls)


# ==========================================================================
# QuantSpec — QConfig's structured per-path quantizer description
# ==========================================================================


@dataclass(frozen=True)
class QuantSpec:
    """Hashable (kind, k, params) triple naming a registered quantizer."""

    kind: str
    k: int = 8
    params: tuple = ()

    def make(self) -> Quantizer:
        return get_quantizer(self.kind, self.k, self.params)

    def replace(self, **kw) -> "QuantSpec":
        return dataclasses.replace(self, **kw)


def spec_from_alias(kind: str, default_k: int = 8) -> QuantSpec:
    """Legacy string kind -> QuantSpec ("sq16" -> sq@16, "flag8" -> flag@8).

    Width-suffixed aliases pin their k (matching the legacy quant_error
    dispatch, which hardcoded them); bare kinds take `default_k`.
    """
    if kind in ALIASES:
        name, fixed_k = ALIASES[kind]
        return QuantSpec(name, fixed_k if fixed_k is not None else default_k)
    if kind not in _REGISTRY:
        raise ValueError(
            f"unknown quantizer {kind!r}; registered: {registered_quantizers()}")
    return QuantSpec(kind, default_k)


def legacy_kind(spec: QuantSpec) -> str:
    """Canonical legacy string for a spec (for the deprecated alias fields)."""
    for alias, (name, fixed_k) in ALIASES.items():
        if name == spec.kind and fixed_k == spec.k:
            return alias
    return spec.kind


def resolve_quantizer(spec, default_k: int = 8) -> Quantizer:
    """QuantSpec | legacy string | Quantizer -> Quantizer instance."""
    if isinstance(spec, Quantizer):
        return spec
    if isinstance(spec, QuantSpec):
        return spec.make()
    return spec_from_alias(spec, default_k).make()


# ==========================================================================
# quantize with straight-through estimator (paper Eq. 1), QTensor-valued
# ==========================================================================


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def quantize_ste(quantizer: Quantizer, x: Array) -> QTensor:
    """QTensor = quantizer.quantize(x), identity cotangent to x.

    The returned QTensor has a carrier, so it composes with both payload
    consumers (qeinsum native) and fp32 consumers (elementwise math).
    """
    return quantizer.quantize(x).with_carrier()


def _quantize_ste_fwd(quantizer, x):
    return quantize_ste(quantizer, x), None


def _quantize_ste_bwd(quantizer, _, ct):
    return (ct.carrier,)


quantize_ste.defvjp(_quantize_ste_fwd, _quantize_ste_bwd)
