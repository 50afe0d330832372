"""Pure-jnp oracles for every Pallas kernel (hypothesis sweeps assert
kernel == oracle across shapes/dtypes)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _act(name: str):
    return {
        "swiglu": jax.nn.silu,
        "geglu": lambda x: jax.nn.gelu(x, approximate=True),
        "reglu": jax.nn.relu,
    }[name]


def griffin_ffn_ref(
    x: jax.Array,  # [B, D]
    wg: jax.Array,  # [F, D] (neuron-rows)
    w1: jax.Array,  # [F, D]
    w2: jax.Array,  # [F, D]
    block_ids: jax.Array,  # [nb] int32, block granularity
    block_size: int,
    activation: str = "swiglu",
) -> jax.Array:
    """GRIFFIN decode FFN: act(x Wg^T) * (x W1^T) @ W2 over selected
    neuron blocks only.  Returns fp32 [B, D]."""
    idx = (block_ids[:, None] * block_size
           + jnp.arange(block_size, dtype=block_ids.dtype)[None, :]).reshape(-1)
    wg_s = jnp.take(wg, idx, axis=0)
    w1_s = jnp.take(w1, idx, axis=0)
    w2_s = jnp.take(w2, idx, axis=0)
    act = _act(activation)
    g = x @ wg_s.T
    h = x @ w1_s.T
    z = act(g) * h
    return (z @ w2_s).astype(jnp.float32)


def expert_stat_ref(z: jax.Array) -> jax.Array:
    """Eq. 6 squared statistic from activations z [S, F] -> s_sq [F] fp32."""
    zf = z.astype(jnp.float32)
    row = jnp.sum(jnp.square(zf), axis=-1, keepdims=True)
    inv = jnp.where(row > 0, 1.0 / row, 0.0)
    return jnp.sum(jnp.square(zf) * inv, axis=0)


def glu_ffn_ref(x: jax.Array, wg: jax.Array, w1: jax.Array, w2: jax.Array,
                activation: str = "swiglu") -> jax.Array:
    """Dense GLU FFN forward. x [S, D]; wg/w1 [D, F]; w2 [F, D]."""
    act = _act(activation)
    z = act(x @ wg) * (x @ w1)
    return (z @ w2).astype(jnp.float32)


def paged_gather_ref(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Block-table page gather. pool [P, ...]; block_tables [B, n]
    int32 (entries pre-clipped to >= 0) -> [B, n, ...]."""
    return jnp.take(pool, jnp.clip(block_tables, 0), axis=0)


def kv_token_major(pages: jax.Array) -> jax.Array:
    """Gathered KV pages [B, n, KV, page, hd] -> the request's
    token-major view [B, n*page, KV, hd]."""
    B, n, KV, page, hd = pages.shape
    return pages.transpose(0, 1, 3, 2, 4).reshape(B, n * page, KV, hd)


def paged_kv_gather_ref(pool: jax.Array,
                        block_tables: jax.Array) -> jax.Array:
    """pool [P, KV, page, hd]; block_tables [B, n] -> [B, n*page, KV, hd]."""
    return kv_token_major(paged_gather_ref(pool, block_tables))


def paged_attn_ref(
    q: jax.Array,            # [B, S, H, hd]
    k_new: jax.Array,        # [B, S, KV, hd]
    v_new: jax.Array,        # [B, S, KV, hd]
    pool_k: jax.Array,       # [P+1, KV, page, hd]
    pool_v: jax.Array,       # [P+1, KV, page, hd]
    block_tables: jax.Array, # [B, W] int32, -1 = unallocated
    pos: jax.Array,          # [B] int32
    write_mask: jax.Array,   # [B, S] bool
    window: int = 0,
    *,
    scale_k: jax.Array = None,  # [P+1, KV, 1, 1] fp32 (quantized pools)
    scale_v: jax.Array = None,
    kv_dtype: str = "fp32",
):
    """Gather-then-attend oracle for the fused ``paged_attn`` kernel:
    scatter new K/V (masked slots -> trash page), materialize the full
    per-request page view, masked softmax over every position.  The
    same math as ``attention.paged_attn_step``'s fallback path.

    Quantization-aware: for ``kv_dtype`` int8/fp8 the scatter runs the
    page-boundary quantization program from ``kernels/kv_quant.py``
    (monotone per-page-per-head absmax scales, old rows re-encoded
    under grown scales) and attention reads ``bits * scale`` in fp32 —
    the identical float ops as the fused kernel, so pool bits and
    scales match it exactly.

    Returns (ctx [B,S,H,hd] fp32, new_pool_k, new_pool_v) and, when
    quantized, appends (new_scale_k, new_scale_v)."""
    from repro.kernels import kv_quant

    NEG_INF = -2.0e38
    B, S, H, hd = q.shape
    KV = k_new.shape[2]
    G = H // KV
    page = pool_k.shape[2]
    trash = pool_k.shape[0] - 1
    W = block_tables.shape[1]
    quantized = kv_quant.is_quantized(kv_dtype)
    positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    logical_page = positions // page
    offset = positions % page
    gp = jnp.take_along_axis(
        block_tables, jnp.clip(logical_page, 0, W - 1), axis=1
    )
    ok = write_mask & (gp >= 0) & (logical_page < W)
    gp = jnp.where(ok, gp, trash)
    gpf, off = gp.reshape(-1), offset.reshape(-1)
    if quantized:
        new_k, new_sk = kv_quant.quantize_scatter_ref(
            pool_k, scale_k, gpf, off, k_new.reshape(B * S, KV, hd), kv_dtype
        )
        new_v, new_sv = kv_quant.quantize_scatter_ref(
            pool_v, scale_v, gpf, off, v_new.reshape(B * S, KV, hd), kv_dtype
        )
    else:
        new_k = pool_k.at[gpf, :, off].set(
            k_new.reshape(B * S, KV, hd).astype(pool_k.dtype)
        )
        new_v = pool_v.at[gpf, :, off].set(
            v_new.reshape(B * S, KV, hd).astype(pool_v.dtype)
        )
    k_cache = paged_kv_gather_ref(new_k, block_tables)
    v_cache = paged_kv_gather_ref(new_v, block_tables)
    if quantized:
        k_cache = kv_quant.dequantize(
            k_cache, kv_quant.gather_scales(new_sk, block_tables, page)
        )
        v_cache = kv_quant.dequantize(
            v_cache, kv_quant.gather_scales(new_sv, block_tables, page)
        )
    else:
        # attention math always in fp32 (no-op for fp32 pools; bf16
        # pools round on write, upcast on read — same as the kernel)
        k_cache = k_cache.astype(jnp.float32)
        v_cache = v_cache.astype(jnp.float32)
    C = W * page
    scale = 1.0 / (hd ** 0.5)
    qg = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_cache)
    scores = scores.astype(jnp.float32) * scale
    kpos = jnp.arange(C, dtype=jnp.int32)[None, None, :]
    qpos = positions[:, :, None]
    valid = kpos <= qpos
    if window:
        valid &= kpos > qpos - window
    page_alloc = (block_tables >= 0)[:, :, None]
    valid &= page_alloc.repeat(page, axis=2).reshape(B, 1, C)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v_cache.dtype),
                     v_cache)
    ctx = ctx.reshape(B, S, H, hd).astype(jnp.float32)
    if quantized:
        return (ctx, new_k, new_v, new_sk, new_sv)
    return (ctx, new_k, new_v)
