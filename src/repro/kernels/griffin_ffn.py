"""Fused GRIFFIN decode-FFN Pallas kernel over a union-compacted buffer.

The serving path keeps one buffer per FF layer holding the union U of
the live requests' selected neurons (``serving/server.py``), and each
row of the batch carries a 0/1 mask over U: its own kept neurons.  The
kernel reads only the buffer's first ``n_live`` blocks (U's extent),
runs both up-projections, the GLU activation, multiplies z by the row's
mask, and accumulates the down-projection in fp32.  A masked neuron
contributes an exact zero, so each row gets exactly the sum over its
own neurons; only the summation order differs from a per-row compacted
matmul.

Layout: weights are neuron-row-major ``[L, C, D]`` (a block of neurons
is a contiguous ``[BK, D]`` tile), stacked over the ``L`` layers of a
scan segment.  The layer index and the live count are scalar-prefetched:
the index maps read layer ``layer`` directly from the stacked buffer, so
no per-layer slice of the weights is ever copied.  The grid runs over
all ``C / BK`` blocks, so the program does not depend on |U|; steps past
the live count clamp their block index to the last live block (the
pipeline starts no new DMA for an unchanged block) and skip their
compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.ref import _act


def _kernel(meta_ref, x_ref, m_ref, wg_ref, w1_ref, w2_ref, y_ref, *,
            activation: str):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(i < meta_ref[1])
    def _block():
        x = x_ref[...]  # [R, D]
        nt = (((1,), (1,)), ((), ()))
        g = jax.lax.dot_general(x, wg_ref[...], nt,
                                preferred_element_type=jnp.float32)
        h = jax.lax.dot_general(x, w1_ref[...], nt,
                                preferred_element_type=jnp.float32)
        z = (_act(activation)(g) * h * m_ref[...]).astype(x.dtype)
        y_ref[...] += jax.lax.dot_general(
            z, w2_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "activation", "interpret"),
)
def griffin_ffn(
    x: jax.Array,  # [R, D]
    wg: jax.Array,  # [L, C, D] neuron rows, stacked over layers
    w1: jax.Array,  # [L, C, D]
    w2: jax.Array,  # [L, C, D]
    mask: jax.Array,  # [R, C] float32 0/1: each row's neurons in the buffer
    layer: jax.Array,  # int32 scalar: which of the L layers
    n_live: jax.Array,  # int32 scalar: blocks of the buffer in use
    *,
    block_size: int = 128,
    activation: str = "swiglu",
    interpret: bool | None = None,
) -> jax.Array:
    """y[r] = sum over the first ``n_live`` blocks of
    ``(act(x wg^T) * (x w1^T) * mask[r]) @ w2`` for layer ``layer``.
    Returns fp32 ``[R, D]``."""
    R, D = x.shape
    C = wg.shape[1]
    assert C % block_size == 0, (C, block_size)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(n_live, jnp.int32)])

    def blk(i, meta):
        # past the live count: stay on the last live block (no new DMA)
        return jnp.maximum(jnp.minimum(i, meta[1] - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C // block_size,),
        in_specs=[
            pl.BlockSpec((R, D), lambda i, meta: (0, 0)),
            pl.BlockSpec((R, block_size), lambda i, meta: (0, blk(i, meta))),
        ] + [
            pl.BlockSpec((None, block_size, D),
                         lambda i, meta: (meta[0], blk(i, meta), 0))
            for _ in range(3)
        ],
        out_specs=pl.BlockSpec((R, D), lambda i, meta: (0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="griffin_ffn",
    )(meta, x, mask.astype(jnp.float32), wg, w1, w2)
