"""Integer gradient compression collectives (shard_map + ppermute ring).

The paper's CQ already puts weight gradients on a 15-bit grid with a shared
power-of-two scale — so the gradient wire format can be an integer QTensor
(int16 halves f32 traffic, int8 quarters it) with NO extra information loss
beyond what WAGEUBN's own optimizer quantization discards.  We implement the
ring reduce-scatter manually so every hop's message really is the integer
payload on the wire (XLA's native all-reduce would keep the accumulator
dtype on the wire).

The wire format IS a QTensor: `_wire_quantize` decomposes the local chunks
once into (int payload, shared pow2 scale) and the ring ships the payload;
`wire_quantize` is exported for tests and for QTensor-native callers that
want to hand the payload to other transports.

Overflow control: with n contributions, partial sums of b-bit operands need
b + ceil(log2 n) bits; `wire_quantize` pre-shifts the grid by `shift` and
clips payloads to `wire_limit(bits, shift)` = 2^(bits-1-shift) - 1, so ANY
partial sum of up to 2^shift payloads stays strictly inside the signed wire
width (tests/test_qtensor.py proves the bound by property for n <= 256).
The discarded low bits are below CQ's own grid once divided by n —
documented trade-off.

Staged widening (`wire_plan`): when the fan-in bound fails (shift > bits-2,
e.g. 4-bit wires at dp*n_shards >= 8), the payload keeps (nearly) full
`bits`-bit resolution and the partial sums ride int16 hops instead — the
exact-integer-sum guarantee is unchanged, only the hop dtype widens.  A
hard error remains only when even int16 cannot carry the fan-in
(shift > 14, i.e. > 16384-way sums).

Two layers of API:

  outer wrappers (`compressed_psum_int`, `ring_reduce_scatter_int`) own
  their shard_map — drop-in collectives for replicated callers.

  in-body primitives (`ring_allreduce_int`, `wire_sync_mean`,
  `wire_sync_tree`) run INSIDE an enclosing shard_map (the sharded training
  step, launch/train.py): the caller already holds per-device values and an
  axis name.  `wire_sync_mean` is the per-leaf DP-invariant gradient sync
  (DESIGN.md §9): payload rounding happens per VIRTUAL shard against a
  globally pmax'ed pow2 scale with a shift derived from the STATIC shard
  count, and every cross-device reduction is an exact integer sum — so the
  result is bitwise independent of how the virtual shards are laid out over
  devices.  `wire_sync_tree` is the same algorithm restructured for
  wall-clock (DESIGN.md §13): one stacked pmax for all leaves, the payload
  round/clip fused into the local pre-sum, and a single double-buffered
  ring over the concatenated pre-sums whose int8 hops pack two-per-int16 —
  bitwise identical outputs, a fraction of the collectives.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import qfuncs as qf
from repro.core.qtensor import QTensor, payload_dtype


def wire_shift(n: int) -> int:
    """Grid pre-shift covering n-way partial sums: ceil(log2 n)."""
    return max(0, math.ceil(math.log2(max(n, 1))))


def wire_limit(bits: int, shift: int) -> float:
    """Largest payload magnitude such that any partial sum of up to 2^shift
    payloads stays strictly inside the signed `bits`-wide wire dtype.

    Raises when the wire is too narrow to carry ANY signal at that fan-in
    (shift > bits - 2, e.g. 256-way sums on an int8 wire): silently clipping
    every payload to zero would be a correctness bug dressed as compression.
    """
    if shift > bits - 2:
        raise ValueError(
            f"{bits}-bit wire cannot carry {2 ** shift}-way partial sums "
            f"(need shift <= bits - 2 = {bits - 2}, got {shift}); "
            f"wire_plan() stages such fan-ins onto int16 hops instead")
    return 2.0 ** (bits - 1 - shift) - 1.0


def wire_plan(bits: int, shift: int) -> tuple[int, int]:
    """Resolve how `bits`-bit payloads survive a 2^shift-way fan-in.

    Returns (clip_shift, hop_bits):

      classic   — shift <= bits - 2: the grid pre-shift is absorbed by the
        payload clip (`wire_limit(bits, shift)`) and partial sums ride hops
        of the payload width itself (hop_bits == bits).
      staged widening — narrow wires at large fan-in (e.g. 4-bit payloads
        summed 8-way) would otherwise clip every payload to zero.  Instead
        the payload keeps full `bits`-bit resolution minus only what int16
        cannot absorb (clip_shift = max(0, shift + bits - 16)) and the
        partial sums ride int16 hops: |payload| <= 2^(bits-1-clip_shift)-1,
        so any sum of up to 2^shift payloads is < 2^15 - exact on an int16
        hop, and < 2^24 so the f32 pre-sum accumulation is also exact.

    Raises only when int16 hops cannot carry the fan-in either
    (clip_shift > bits - 2, i.e. shift > 14).
    """
    if shift <= bits - 2:
        return shift, bits
    clip_shift = max(0, shift + bits - 16)
    if clip_shift > bits - 2:
        raise ValueError(
            f"{bits}-bit payloads cannot survive {2 ** shift}-way partial "
            f"sums even on an int16 hop (needs shift <= 14, got {shift})")
    return clip_shift, 16


def _clip_limit_f32(bits: int, shift: int) -> np.float32:
    """wire_limit as an f32 clip bound that never exceeds the true bound.

    The clip runs in f32, where wide limits (bits=32) are not exactly
    representable — 2^30 - 1 would round UP to 2^30 and let payloads
    escape the partial-sum bound — so the bound is lowered to the nearest
    f32 at or below it (identical for bits <= 24).
    """
    lim = wire_limit(bits, shift)
    limf = np.float32(lim)
    if float(limf) > lim:                  # f32 rounded up: step back one ulp
        limf = np.nextafter(limf, np.float32(0.0), dtype=np.float32)
    return limf


def wire_quantize(chunks, amax, bits: int, shift: int) -> QTensor:
    """Decompose gradient chunks into the integer wire QTensor.

    scale = pow2_ceil(amax) * 2^(1 - bits + clip_shift): the effective
    pre-shift (`wire_plan` — the full `shift` on the classic path, the
    int16-staged remainder otherwise) keeps n-way partial sums inside the
    HOP width (payloads clip to `wire_limit(bits, clip_shift)`, so the
    bound holds even at the saturate-at-pow2-amax corner).  `amax` must
    already be the global max across participating shards (pmax'ed by the
    caller).
    """
    clip_shift, _ = wire_plan(bits, shift)
    limf = _clip_limit_f32(bits, clip_shift)
    scale = qf.pow2_ceil(amax) * 2.0 ** (1 - bits + clip_shift)
    data = jnp.clip(jnp.round(chunks / scale), -limf,
                    limf).astype(payload_dtype(bits))
    return QTensor(data, scale, bits)


def wire_presum(g, amax, bits: int, shift: int):
    """Fused payload round/clip + local pre-sum — no payload tensor.

    Same grid and clip as `wire_quantize` over g: (vs_local, *shape), but
    the per-shard integer payloads are summed over axis 0 IN the producing
    expression: round and clip feed the reduction directly, so no
    (vs_local, *shape) integer tensor is ever materialized (XLA fuses
    elementwise producers into reductions; the jaxpr acceptance test in
    tests/test_sharded_train.py checks no such tensor exists).

    Exactness: rounded/clipped payloads are integers with magnitude
    <= 2^(bits-1-clip_shift), and summing up to 2^shift of them stays
    below 2^(hop_bits-1) (wire_plan's invariant, classic or staged).  For
    bits <= 16 that is < 2^24, exactly representable in f32, so the f32
    accumulation equals the integer sum bit for bit.  Wider wires can pass
    2^24, where f32 addition rounds — those sum the materialized int32
    payload instead (same values, exact by dtype).

    Returns (int32 pre-sum of shape g.shape[1:], pow2 wire scale).
    """
    clip_shift, _ = wire_plan(bits, shift)
    limf = _clip_limit_f32(bits, clip_shift)
    scale = qf.pow2_ceil(amax) * 2.0 ** (1 - bits + clip_shift)
    vals = jnp.clip(jnp.round(g / scale), -limf, limf)
    if bits > 16:
        return jnp.sum(vals.astype(jnp.int32), axis=0), scale
    return jnp.sum(vals, axis=0).astype(jnp.int32), scale


def pack_int8_pairs(x):
    """Pack consecutive int8 pairs two-per-int16 (the wire-bits=8 codec).

    x: (..., 2m) int8 -> (..., m) int16 with element i carrying
    (x[2i] in the low byte, x[2i+1] in the high byte).  The low byte rides
    as its two's-complement bit pattern (uint8 view), so every value
    including -128 round-trips exactly through `unpack_int16_pairs`.
    """
    lo = x[..., 0::2].astype(jnp.uint8).astype(jnp.int16)
    hi = x[..., 1::2].astype(jnp.int16) << 8
    return hi | lo


def unpack_int16_pairs(p):
    """Inverse of `pack_int8_pairs`: (..., m) int16 -> (..., 2m) int8.

    Low byte recovers through the uint8 view (wrap-on-cast restores the
    sign, -128 included); high byte through an arithmetic shift.
    """
    lo = (p & 0xFF).astype(jnp.uint8).astype(jnp.int8)
    hi = (p >> 8).astype(jnp.int8)
    return jnp.stack([lo, hi], axis=-1).reshape(p.shape[:-1] + (-1,))


def _ring_reduce_scatter(qt: QTensor, axis_name, n, hop_bits: int | None = None):
    """qt.data: (n, chunk) integer contributions per rank.

    Classic ring: rank r starts with its contribution to chunk (r-1)%n and
    after n-1 hops holds the fully reduced chunk r.  Every message on the
    wire is the `hop_bits` integer dtype (default: the payload width;
    staged widening passes 16 to carry sub-8 payload sums), never fp32.
    """
    x_int = qt.data
    hop_bits = qt.k if hop_bits is None else hop_bits
    # clip in the int32 domain: float bounds near 2^31 are not exactly
    # representable in f32 and would promote the accumulator
    lim = jnp.asarray(min(2 ** (hop_bits - 1) - 1, 2 ** 31 - 1), jnp.int32)
    dtype = payload_dtype(hop_bits)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = jnp.take(x_int, (idx - 1) % n, axis=0).astype(jnp.int32)

    def hop(i, acc):
        msg = jnp.clip(acc, -lim, lim).astype(dtype)   # integer wire
        msg = lax.ppermute(msg, axis_name, perm)
        k = (idx - 2 - i) % n
        return msg.astype(jnp.int32) + jnp.take(x_int, k, axis=0)

    acc = lax.fori_loop(0, n - 1, hop, acc) if n > 1 else acc
    return acc


def ring_reduce_scatter_int(x, mesh, axis_name: str, bits: int = 16):
    """Reduce-scatter x (replicated-shape per device) over `axis_name`,
    quantizing every wire message to the `bits`-wide integer payload.
    Returns the per-device shard of the mean, fp32.
    """
    n = mesh.shape[axis_name]
    shift = wire_shift(n)
    _, hop_bits = wire_plan(bits, shift)

    def f(xl):
        flat = xl.reshape(-1)
        pad = -flat.size % n
        flat = jnp.pad(flat, (0, pad))
        chunks = flat.reshape(n, -1)
        amax = lax.pmax(jnp.max(jnp.abs(chunks)), axis_name)
        qt = wire_quantize(chunks, amax, bits, shift)
        acc = _ring_reduce_scatter(qt, axis_name, n, hop_bits)
        return acc.astype(jnp.float32) * qt.scale / n

    spec = P(*((None,) * x.ndim))
    fn = jax.shard_map(f, mesh=mesh, in_specs=(spec,),
                       out_specs=P(axis_name), check_vma=False)
    return fn(x)


def compressed_psum_int(x, mesh, axis_name: str, bits: int = 16):
    """integer-wire all-reduce mean = ring reduce-scatter + all-gather."""
    n = mesh.shape[axis_name]
    shift = wire_shift(n)
    _, hop_bits = wire_plan(bits, shift)

    def f(xl):
        shape = xl.shape
        flat = xl.reshape(-1)
        pad = -flat.size % n
        flat = jnp.pad(flat, (0, pad))
        chunks = flat.reshape(n, -1)
        amax = lax.pmax(jnp.max(jnp.abs(chunks)), axis_name)
        qt = wire_quantize(chunks, amax, bits, shift)
        acc = _ring_reduce_scatter(qt, axis_name, n, hop_bits)
        # all-gather the reduced chunks; rank i holds chunk i so rank order
        # IS chunk order
        gathered = lax.all_gather(acc, axis_name, axis=0)  # (n, chunk)
        full = gathered.reshape(-1)
        full = full[: flat.size - pad] if pad else full
        return (full.astype(jnp.float32) * qt.scale / n).reshape(shape)

    spec = P(*((None,) * x.ndim))
    fn = jax.shard_map(f, mesh=mesh, in_specs=(spec,), out_specs=spec,
                       check_vma=False)
    return fn(x)


# --------------------------------------------------------------------------
# in-body primitives (run INSIDE an enclosing shard_map)
# --------------------------------------------------------------------------


def ring_allreduce_int(x, axis_name: str, n: int, bits: int, *,
                       pack: bool = False, buckets: int = 1):
    """Exact integer all-reduce-sum of per-device int32 contributions.

    Ring reduce-scatter (messages in the `bits`-wide wire dtype) followed by
    an integer all-gather.  The caller guarantees every partial sum fits the
    wire width — the contract `wire_quantize` establishes via its shift/clip
    — so the per-hop dtype cast never wraps and the sum is exact.  Must run
    inside shard_map with `axis_name` manual; `n` is the axis size.

    `bits` is the HOP width — the payload width on the classic path,
    16 when `wire_plan` staged a narrower payload onto int16 hops.

    pack (int8-dtype hops, i.e. bits <= 8): consecutive int8 payload pairs
    ride two-per-int16, halving each hop's on-wire message element count —
    pack/unpack is a lossless bit-pattern transform, so the sum is
    unchanged.  buckets=2 double-buffers the ring: each chunk splits in
    two and BOTH buckets' ppermutes are issued before either received
    message is consumed, so a hop's send overlaps the other bucket's
    accumulate (and gives the compiler two in-flight transfers to overlap
    with whatever compute surrounds the sync).  Bucket order is restored
    before the all-gather — the reduced values are identical for any
    bucket count.
    """
    assert not (pack and bits > 8), "pair packing needs int8-dtype hops"
    dtype = payload_dtype(bits)
    shape = x.shape
    flat = x.reshape(-1)
    unit = n * buckets * (2 if pack else 1)
    pad = -flat.size % unit
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, buckets, -1)   # chunk r = buckets row-slices
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    start = jnp.take(chunks, (idx - 1) % n, axis=0).astype(jnp.int32)
    accs = tuple(start[b] for b in range(buckets))

    def to_wire(a):
        a = a.astype(dtype)
        return pack_int8_pairs(a) if pack else a

    def from_wire(m):
        return (unpack_int16_pairs(m) if pack else m).astype(jnp.int32)

    def hop(i, accs):
        # double-buffered: every bucket's ppermute is issued before any
        # received message feeds an add
        msgs = [lax.ppermute(to_wire(a), axis_name, perm) for a in accs]
        nxt = jnp.take(chunks, (idx - 2 - i) % n, axis=0)
        return tuple(from_wire(m) + nxt[b] for b, m in enumerate(msgs))

    accs = lax.fori_loop(0, n - 1, hop, accs) if n > 1 else accs
    acc = (jnp.concatenate([a.reshape(-1) for a in accs])
           if buckets > 1 else accs[0].reshape(-1))
    full = lax.all_gather(acc, axis_name, axis=0).reshape(-1)
    full = full[: flat.size - pad] if pad else full
    return full.reshape(shape)


def wire_sync_mean(g, axis_name: str, *, n_shards: int, n_dev: int,
                   bits: int = 16):
    """DP-invariant integer-wire mean of per-virtual-shard contributions.

    g: (vs_local, *shape) f32 — this device's virtual-shard gradient
    contributions.  Returns (*shape,) f32: the mean over all `n_shards`
    virtual shards across the `axis_name` axis (size `n_dev`).

    Bit-exactness contract (DESIGN.md §9): the ONE cross-device scale
    reduction is the lax.pmax on the shard-local amax; payload rounding
    happens per VIRTUAL shard against that shared pow2 scale with
    shift = ceil(log2 n_shards) (a STATIC property of the algorithm, not of
    the device layout), and both the local pre-sum and the ring are exact
    integer additions.  Every quantity is therefore a pure function of
    (n_shards, global batch) — how the virtual shards map onto devices
    cannot change a single bit of the result.
    """
    shift = wire_shift(n_shards)
    _, hop_bits = wire_plan(bits, shift)
    with jax.named_scope("wire"):
        amax = lax.pmax(jnp.max(jnp.abs(g)), axis_name)
        qt = wire_quantize(g, amax, bits, shift)
        local = jnp.sum(qt.data.astype(jnp.int32), axis=0)
        total = ring_allreduce_int(local, axis_name, n_dev, hop_bits)
        return total.astype(jnp.float32) * qt.scale / n_shards


def wire_sync_tree(grads, axis_name: str, *, n_shards: int, n_dev: int,
                   bits: int = 16):
    """Whole-tree integer-wire gradient sync — the packed wire codec.

    Value-identical to mapping `wire_sync_mean` over the tree (same amax,
    same grid, same exact integer sums — tests prove bitwise equality),
    but shaped for wall-clock instead of per-leaf dispatch:

      * ONE stacked scale reduction: every leaf's local amax pmaxes in a
        single (n_leaves,)-shaped collective instead of n_leaves scalar
        pmaxes (pmax is elementwise, so each lane equals its scalar run).
      * fused pre-sum (`wire_presum`): each leaf's payload round/clip
        feeds its local shard-sum directly — no per-shard integer payload
        tensor is materialized.
      * ONE ring: the int32 pre-sums concatenate into a flat buffer that
        rides a single double-buffered ring + all-gather — 2(n_dev-1)
        ppermutes and one gather per STEP, not per leaf.  At wire-bits=8
        the hop messages pack two-per-int16 (`pack_int8_pairs`), halving
        the on-wire element count.

    grads: pytree of (vs_local, *shape) f32 per-virtual-shard sums.
    Returns the matching pytree of (*shape,) f32 means over all
    `n_shards` virtual shards.
    """
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        return grads
    with jax.named_scope("wire"):
        shift = wire_shift(n_shards)
        _, hop_bits = wire_plan(bits, shift)
        amax = lax.pmax(
            jnp.stack([jnp.max(jnp.abs(g)) for g in leaves]), axis_name)
        presums, scales, shapes = [], [], []
        for i, g in enumerate(leaves):
            ps, scale = wire_presum(g, amax[i], bits, shift)
            presums.append(ps.reshape(-1))
            scales.append(scale)
            shapes.append(ps.shape)
        flat = (jnp.concatenate(presums) if len(presums) > 1 else presums[0])
        total = ring_allreduce_int(flat, axis_name, n_dev, hop_bits,
                                   pack=(hop_bits <= 8),
                                   buckets=2 if n_dev > 1 else 1)
        outs, off = [], 0
        for shape, scale in zip(shapes, scales):
            size = int(np.prod(shape)) if shape else 1
            seg = total[off:off + size]
            # same float expression as wire_sync_mean -> bitwise-equal means
            outs.append((seg.astype(jnp.float32) * scale
                         / n_shards).reshape(shape))
            off += size
        return jax.tree.unflatten(treedef, outs)


def default_wire_codec(backend: str | None = None) -> tuple[str, str]:
    """Backend-aware `--wire-codec auto` resolution.  Returns (codec, why).

    The packed whole-tree codec halves on-wire elements and issues 2
    ppermutes/step — a win where transfers are real DMAs (TPU) — but on the
    CPU backend XLA serializes ppermutes, so the single big packed ring
    wall-clocks SLOWER than per-leaf rings even as the wire work halves
    (the measured PR 9 caveat, BENCH_train train/wire_codec).  Both codecs
    are bitwise-identical, so the default can follow the backend freely.
    """
    backend = backend or jax.default_backend()
    if backend == "tpu":
        return "packed", "tpu: 2x fewer on-wire elements, 2 ppermutes/step"
    return "leaf", (f"{backend}: serialized ppermutes make the packed "
                    "single-ring slower than per-leaf rings")
