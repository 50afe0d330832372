"""Paged KV gather Pallas kernel (TPU-native block-table reads).

The serving subsystem stores decode KV in a shared pool of fixed-size
pages ([P+1, KV, page, hd] per layer: each head's page is one
[page, hd] tile); each request addresses its logical positions through a
block table of page ids.  This kernel materializes the per-request
contiguous KV view: grid (B, n_pages), with the *scalar-prefetched*
block table driving the input BlockSpec index map — so each grid step
DMAs exactly one page from HBM into VMEM and copies it to the output
row.  Only pages a request actually owns are ever read (the pruning /
paging analogue of the GRIFFIN zero-copy weight gather in
``griffin_ffn.py``).

Unallocated table entries must be clipped to a valid page id by the
caller (the attention mask hides their contents downstream).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _kernel(bt_ref, pool_ref, out_ref):
    out_ref[...] = pool_ref[...].reshape(out_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gather(
    pool: jax.Array,  # [P, *page_shape]
    block_tables: jax.Array,  # [B, n] int32 page ids (pre-clipped to >= 0)
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns [B, n, *page_shape]: row (b, i) = pool[block_tables[b, i]].
    A block is one whole page, so any page shape is Mosaic-legal."""
    page_shape = pool.shape[1:]
    B, n = block_tables.shape
    zeros = (0,) * len(page_shape)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n),
        in_specs=[
            pl.BlockSpec((1,) + page_shape,
                         lambda b, i, bt: (bt[b, i],) + zeros),
        ],
        out_specs=pl.BlockSpec((1, 1) + page_shape,
                               lambda b, i, bt: (b, i) + zeros),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n) + page_shape, pool.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables, pool)
