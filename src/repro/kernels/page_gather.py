"""Pallas TPU kernel: paged KV-cache gather (the serving-engine hot loop).

page_gather — copy the physical int8 pages named by a per-lane page table
into a contiguous per-lane view: pages (P, page, D) + table (B, NB) ->
(B, NB, page, D).  The whole move stays int8 — the gathered view is the
payload the decode attention matmuls consume directly (no dequantize).

The page id for each (lane, block) grid cell is data-dependent, so the
input block index comes from a scalar-prefetch operand
(pltpu.PrefetchScalarGridSpec): the table is available before the kernel
body runs and drives the HBM->VMEM DMA of exactly one page per cell.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(table_ref, pages_ref, out_ref):
    # pages_ref already holds the page selected by the index_map below
    out_ref[0, 0] = pages_ref[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_gather(pages: jax.Array, table: jax.Array, *,
                interpret: bool = True) -> jax.Array:
    """pages: (P, page, D) int8; table: (B, NB) int32 -> (B, NB, page, D).

    Out-of-range page ids are clamped (id 0 is the engine's trash page, so
    dead lanes gather garbage that the attention mask never reads).
    """
    p, page, d = pages.shape
    b, nb = table.shape
    table = jnp.clip(table, 0, p - 1).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, page, d),
                               lambda i, j, tref: (tref[i, j], 0, 0))],
        out_specs=pl.BlockSpec((1, 1, page, d),
                               lambda i, j, tref: (i, j, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nb, page, d), pages.dtype),
        name="page_gather",
        interpret=interpret,
    )(table, pages)
