"""Public jit'd wrappers for the Pallas kernels.

Backend selection is centralized in ``kernels/backend.py``: every
kernel takes ``interpret=None`` and resolves it through
``default_interpret()`` — compile to Mosaic on TPU, interpret
everywhere else (the CPU tests validate them against ``ref.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.backend import default_interpret  # re-export  # noqa: F401
from repro.kernels.expert_stat import expert_stat as _expert_stat
from repro.kernels.glu_ffn import glu_ffn as _glu_ffn
from repro.kernels.griffin_ffn import griffin_ffn as _griffin_ffn
from repro.kernels.paged_attn import paged_attn as _paged_attn
from repro.kernels.paged_gather import paged_gather as _paged_gather


def griffin_ffn_decode(x, wg, w1, w2, mask, layer, n_live, *,
                       block_size: int = 128, activation: str = "swiglu"):
    """GRIFFIN decode FFN over a union-compacted buffer, reading only its
    first ``n_live`` blocks (see kernels/griffin_ffn.py)."""
    return _griffin_ffn(
        x, wg, w1, w2, mask, layer, n_live, block_size=block_size,
        activation=activation,
    )


def griffin_stat(z):
    """Fused eq. 6 statistic. z: [S, F] or [B, S, F]."""
    if z.ndim == 3:
        return jax.vmap(lambda zz: _expert_stat(zz))(z)
    return _expert_stat(z)


def glu_ffn_forward(x, wg, w1, w2, *, activation: str = "swiglu"):
    """Dense GLU FFN forward. x: [S, D]."""
    return _glu_ffn(x, wg, w1, w2, activation=activation)


def paged_gather(pool, block_tables):
    """Block-table page gather. pool [P, ...]; bt [B, n] -> [B, n, ...]."""
    return _paged_gather(pool, jnp.clip(block_tables, 0))


def paged_kv_gather(pool, block_tables):
    """KV-shaped wrapper: pool [P, KV, page, hd] -> [B, n*page, KV, hd].

    The kernel copies whole pages (every head's [page, hd] tile); the
    token-major view is one transpose of the gathered pages.
    """
    return ref.kv_token_major(paged_gather(pool, block_tables))


def paged_attention(q, k_new, v_new, pool_k, pool_v, block_tables, pos,
                    write_mask, *, scale_k=None, scale_v=None,
                    kv_dtype: str = "fp32", window: int = 0):
    """Fused paged-attention decode step (see kernels/paged_attn.py):
    in-kernel K/V scatter + online-softmax attention streaming only the
    pages each request owns.  Returns (ctx [B,S,H,hd] fp32, new_pool_k,
    new_pool_v) — for quantized ``kv_dtype`` (int8/fp8) the page-scale
    pools go in and come back too, appended as (new_scale_k,
    new_scale_v).  Pools and scale pools are updated in place
    (input/output aliased)."""
    return _paged_attn(
        q, k_new, v_new, pool_k, pool_v, block_tables, pos, write_mask,
        scale_k=scale_k, scale_v=scale_v, kv_dtype=kv_dtype,
        window=window,
    )


# re-export oracles for tests
griffin_ffn_ref = ref.griffin_ffn_ref
expert_stat_ref = ref.expert_stat_ref
glu_ffn_ref = ref.glu_ffn_ref
paged_gather_ref = ref.paged_gather_ref
paged_kv_gather_ref = ref.paged_kv_gather_ref
paged_attn_ref = ref.paged_attn_ref
