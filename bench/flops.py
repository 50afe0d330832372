"""Operations and bytes that the served work needs, from shapes.

These are the least the algorithm must do, not what an implementation
happens to do: attention reads each attended key and value once and
writes each new one once; a token's matmuls touch each weight once.  A
roofline share computed from them therefore cannot pass 100% unless
the device time leaves work out.
"""
from __future__ import annotations

from bench.spec import ModelSpec

KV_BYTES = 2  # bf16 pages (the served dtype)
CTX_BYTES = 4  # the kernel returns float32 context


def attn_call(s: ModelSpec, pos: int, S: int):
    """(flops, bytes) of one paged-attention call for one request row
    and one layer: ``S`` new tokens after ``pos`` cached ones."""
    keys = S * pos + S * (S + 1) // 2  # causal (query, key) pairs
    flops = 4 * s.heads * s.head_dim * keys  # QK^T and PV
    # read the ``pos`` cached keys and values, write the ``S`` new ones
    kv_rw = (2 * s.kv_heads * s.head_dim * KV_BYTES) * (pos + S)
    q = S * s.heads * s.head_dim * KV_BYTES
    new_kv_in = 2 * S * s.kv_heads * s.head_dim * KV_BYTES
    out = S * s.heads * s.head_dim * CTX_BYTES
    return flops, kv_rw + q + new_kv_in + out


def token_flops(s: ModelSpec, ctx: int, k_ff: int) -> float:
    """Model FLOPs of one token at context ``ctx`` (keys it attends),
    with ``k_ff`` FF neurons per layer (``d_ff`` dense)."""
    D, H, KV, hd = s.d_model, s.heads, s.kv_heads, s.head_dim
    proj = D * H * hd * 2 + 2 * D * KV * hd  # q, o; k, v
    ffn = 3 * D * k_ff
    attn = 4 * H * hd * ctx
    return s.layers * (2 * (proj + ffn) + attn) + 2 * D * s.vocab


def tier_k(s: ModelSpec, tier: float) -> int:
    """GRIFFIN's kept neurons at a tier: ``round(d_ff * tier)``."""
    return max(1, min(s.d_ff, int(round(s.d_ff * tier))))
