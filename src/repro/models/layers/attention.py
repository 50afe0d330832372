"""GQA/MQA attention with exact chunked-causal prefill and ring-buffer
sliding-window decode caches.

Design notes (TPU adaptation):

* Prefill/training attention is computed in **static query chunks**
  (default 1024), unrolled at trace time.  Chunk ``i`` only reads keys
  ``[k_start, (i+1)*chunk)`` with ``k_start`` floor-clamped by the sliding
  window for local layers — so causal FLOPs are ~S^2/2 (not S^2) and
  local-attention FLOPs are O(S*window), with *static* slice shapes
  (no dynamic control flow in the HLO; plays well with GSPMD).
* Local (sliding-window) layers cache only ``window`` KV entries in a
  ring buffer — this is what keeps gemma3/recurrentgemma ``long_500k``
  decode caches small.
* All softmax math in fp32; matmuls stay in the activation dtype so the
  MXU roofline terms reflect bf16.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain, psum_if_tp
from repro.models.common import apply_rope, rms_normalize
from repro.models.param import ParamSpec

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def attn_specs(cfg) -> Dict[str, ParamSpec]:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec((D, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((D, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((D, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, hd, D), ("heads", "head_dim", "embed")),
    }
    if cfg.use_bias:
        specs["bq"] = ParamSpec((H, hd), ("heads", "head_dim"), init="zeros")
        specs["bk"] = ParamSpec((KV, hd), ("kv_heads", "head_dim"), init="zeros")
        specs["bv"] = ParamSpec((KV, hd), ("kv_heads", "head_dim"), init="zeros")
        specs["bo"] = ParamSpec((D,), ("act_embed",), init="zeros")
    if getattr(cfg, "qk_norm", False):
        specs["q_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
        specs["k_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
    return specs


def _project_qkv(params, x, positions, cfg, use_rope: bool):
    """x: [B,S,D] -> q [B,S,H,hd], k,v [B,S,KV,hd] (rope applied)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if "q_norm" in params:
        q = rms_normalize(q) * params["q_norm"].astype(q.dtype)
        k = rms_normalize(k) * params["k_norm"].astype(k.dtype)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _out_proj(params, ctx, cfg):
    """ctx: [B,S,H,hd] -> [B,S,D].

    Under shard_map tensor parallelism (``sharding.tp_axis`` active)
    the head axis is sharded, so the contraction over ``h`` yields a
    partial sum — all-reduced across shards before the (replicated)
    bias so the bias is counted exactly once.
    """
    y = jnp.einsum("bshk,hkd->bsd", ctx, params["wo"])
    y = psum_if_tp(y)
    if "bo" in params:
        y = y + params["bo"]
    return y


# ---------------------------------------------------------------------------
# Core attention math (pre-projected q/k/v)
# ---------------------------------------------------------------------------

def _grouped_scores(q, k):
    """q: [B,Sq,KV,G,hd], k: [B,Sk,KV,hd] -> [B,KV,G,Sq,Sk] (fp32)."""
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k)
    return s.astype(jnp.float32)


def _attend(q, k, v, mask, scale):
    """q [B,Sq,KV,G,hd]; k,v [B,Sk,KV,hd]; mask [Sq,Sk] or [B,1,1,Sq,Sk]."""
    scores = _grouped_scores(q, k) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
    return ctx


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    window: int = 0,
    q_chunk: int = 1024,
    q_offset: int = 0,
) -> jax.Array:
    """Exact attention over full sequences, chunked over queries.

    q: [B,S,H,hd]; k,v: [B,Sk,KV,hd].  Returns [B,S,H,hd].
    ``q_offset`` is the absolute position of q[.,0] relative to k[.,0].
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, KV, G, hd)

    chunk = min(q_chunk, S)
    if S % chunk != 0:  # pad to a multiple (rare: tiny smoke shapes)
        pad = chunk - S % chunk
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    n_chunks = qg.shape[1] // chunk

    outs = []
    for i in range(n_chunks):
        q_i = qg[:, i * chunk : (i + 1) * chunk]
        q_lo = q_offset + i * chunk
        q_hi = q_lo + chunk
        if causal:
            k_end = min(Sk, q_hi)
            k_start = 0
            if window:
                k_start = max(0, q_lo - window)
        else:
            k_start, k_end = 0, Sk
        k_i = k[:, k_start:k_end]
        v_i = v[:, k_start:k_end]
        qpos = q_lo + np.arange(chunk)[:, None]
        kpos = k_start + np.arange(k_end - k_start)[None, :]
        if causal:
            m = kpos <= qpos
            if window:
                m &= kpos > qpos - window
        else:
            m = np.ones((chunk, k_end - k_start), bool)
        mask = jnp.asarray(m)[None, None, None]
        outs.append(_attend(q_i, k_i, v_i, mask, scale))
    out = jnp.concatenate(outs, axis=1)[:, :S]
    return out.reshape(B, S, H, hd)


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill)
# ---------------------------------------------------------------------------

def attn_forward(
    params: Dict,
    x: jax.Array,
    positions: jax.Array,
    cfg,
    kind: str = "global",
    q_chunk: int = 1024,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Returns (out [B,S,D], (k, v) for cache construction)."""
    use_rope = cfg.family != "encoder"
    q, k, v = _project_qkv(params, x, positions, cfg, use_rope)
    q = constrain(q, ("batch", "seq", "heads", "head_dim"))
    k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
    causal = cfg.is_causal
    window = cfg.sliding_window if kind == "local" else 0
    ctx = chunked_attention(q, k, v, causal=causal, window=window, q_chunk=q_chunk)
    y = _out_proj(params, ctx, cfg)
    return y, (k, v)


# ---------------------------------------------------------------------------
# Decode step against a cache
# ---------------------------------------------------------------------------

def cache_capacity(cfg, kind: str, max_len: int) -> int:
    if kind == "local" and cfg.sliding_window:
        return min(cfg.sliding_window, max_len)
    return max_len


def _kv_int8(cfg) -> bool:
    return getattr(cfg, "kv_cache_int8", False)


def quantize_kv(x: jax.Array):
    """[...,hd] -> (int8 [...,hd], f32 scale [...,1]). Per-(token,head)."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(s, 1e-8)).astype(jnp.int8)
    return q, s


def dequantize_kv(q: jax.Array, s: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * s).astype(dtype)


def init_cache_specs(cfg, kind: str, batch: int, max_len: int) -> Dict[str, ParamSpec]:
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    C = cache_capacity(cfg, kind, max_len)
    seq_ax = "window" if (kind == "local" and cfg.sliding_window) else "kv_seq"
    axes = ("batch", seq_ax, "kv_heads", "head_dim")
    if _kv_int8(cfg):
        # beyond-paper: int8 KV cache — at long-context/large-batch decode
        # the cache read dominates HBM traffic; int8 halves it (scales are
        # 1/hd of the payload)
        return {
            "k": ParamSpec((batch, C, KV, hd), axes, init="zeros", dtype="int8"),
            "v": ParamSpec((batch, C, KV, hd), axes, init="zeros", dtype="int8"),
            "k_scale": ParamSpec((batch, C, KV, 1), axes, init="zeros",
                                 dtype="float32"),
            "v_scale": ParamSpec((batch, C, KV, 1), axes, init="zeros",
                                 dtype="float32"),
        }
    return {
        "k": ParamSpec((batch, C, KV, hd), axes, init="zeros"),
        "v": ParamSpec((batch, C, KV, hd), axes, init="zeros"),
    }


def fill_cache(cache: Dict, k: jax.Array, v: jax.Array) -> Dict:
    """Write prefill K/V [B,S,...] into a cache buffer (static shapes).

    For ring (window) caches the last C entries land at slot ``pos % C``.
    """
    C = cache["k"].shape[1]
    S = k.shape[1]
    int8 = cache["k"].dtype == jnp.int8
    entries = {"k": k, "v": v}
    if int8:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        entries = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    out = {}
    for name, val in entries.items():
        buf = cache[name]
        if S <= C:
            out[name] = jax.lax.dynamic_update_slice_in_dim(buf, val, 0, axis=1)
        else:
            slots = np.arange(S - C, S) % C  # static permutation
            out[name] = buf.at[:, slots].set(val[:, S - C :])
    return out


# ---------------------------------------------------------------------------
# Paged KV cache (block-table serving path)
# ---------------------------------------------------------------------------
#
# The pool holds ``num_pages + 1`` fixed-size pages shared by all live
# requests of one layer; the extra final page is a write-off ("trash")
# target so padded slots / padded chunk tokens can scatter somewhere
# harmless without branching.  A request's logical KV positions map to
# pool pages through its block table (page ids, -1 = unallocated), so
# attention reads are a page gather followed by the exact same masked
# softmax as the contiguous path — unwritten slots are masked to
# NEG_INF, which keeps the math (and, at fp32, the bits) identical.


def paged_cache_specs(cfg, num_pages: int, page_size: int,
                      kv_dtype: str = "fp32") -> Dict[str, ParamSpec]:
    """KV page pool for one attention layer (+1 trash page).

    ``kv_dtype`` (DESIGN.md section 15) picks the page byte format:
    ``fp32`` inherits the model dtype (the pre-quantization pools),
    ``bf16`` halves pool bytes, ``int8``/``fp8`` quarter them and add
    parallel per-page-per-head fp32 *scale pools* (``k_scale`` /
    ``v_scale``) addressed by the same block table.  The scale leaves
    carry the same ``pages``/``kv_heads`` axes as the data, so COW
    page copies (``decoder.copy_pool_pages``'s ``tree.map``), pool
    donation, and TP ``kv_heads`` sharding all treat them as just
    another pool leaf — only the attention kernel/oracle interprets
    them.
    """
    from repro.kernels import kv_quant

    kv_quant.resolve_kv_dtype(kv_dtype)
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    # KV-head-major: one head's page is a contiguous [page, hd] tile,
    # which is the block the fused kernel DMAs (Mosaic wants the last
    # two block dims whole or (8, 128)-aligned)
    axes = ("pages", "kv_heads", "page", "head_dim")
    dt = None if kv_dtype == "fp32" else str(
        kv_quant.pool_jnp_dtype(kv_dtype, cfg.dtype)
    )
    specs = {
        "k": ParamSpec((num_pages + 1, KV, page_size, hd), axes,
                       init="zeros", dtype=dt),
        "v": ParamSpec((num_pages + 1, KV, page_size, hd), axes,
                       init="zeros", dtype=dt),
    }
    if kv_quant.is_quantized(kv_dtype):
        s_axes = ("pages", "kv_heads", None, None)
        specs["k_scale"] = ParamSpec((num_pages + 1, KV, 1, 1), s_axes,
                                     init="zeros", dtype="float32")
        specs["v_scale"] = ParamSpec((num_pages + 1, KV, 1, 1), s_axes,
                                     init="zeros", dtype="float32")
    return specs


def _gather_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """pool [P+1, KV, page, hd], block_tables [B, n] -> [B, n*page, KV, hd].

    On TPU this is the Pallas paged-gather kernel (scalar-prefetched
    block ids drive the BlockSpec index map); off-TPU a plain take.
    """
    from repro.kernels import ops

    if jax.default_backend() == "tpu":
        return ops.paged_kv_gather(pool, block_tables)
    return ops.paged_kv_gather_ref(pool, block_tables)


def resolve_attn_backend(backend: str) -> str:
    """``auto`` -> the fused Pallas kernel on TPU, the gather-then-attend
    oracle elsewhere (bit-exact vs the contiguous path at fp32, which
    the exactness tests pin).  Explicit ``fused``/``gather`` pass
    through — ``fused`` works off-TPU too (interpret mode)."""
    if backend == "auto":
        return "fused" if jax.default_backend() == "tpu" else "gather"
    assert backend in ("fused", "gather"), backend
    return backend


def paged_attn_step(
    params: Dict,
    pool: Dict,
    block_tables: jax.Array,  # [B, n_pages] int32 page ids, -1 = unallocated
    x: jax.Array,  # [B, S, D] new tokens (decode: S=1; prefill chunk: S=chunk)
    pos: jax.Array,  # [B] int32 tokens already cached per request
    write_mask: jax.Array,  # [B, S] bool: which new tokens really exist
    cfg,
    kind: str = "global",
    backend: str = "gather",
    kv_dtype: str = "fp32",
) -> Tuple[jax.Array, Dict]:
    """One paged step: project, scatter new KV into pages, attend.

    Token ``x[b, s]`` sits at absolute position ``pos[b] + s``; its K/V
    land in page ``block_tables[b, (pos[b]+s) // page]`` at offset
    ``(pos[b]+s) % page``.  Returns (y [B,S,D], updated pool).

    ``kv_dtype`` must match the pool (``paged_cache_specs``): for
    int8/fp8 the pool carries ``k_scale``/``v_scale`` leaves and both
    backends run the page-boundary quantization program from
    ``kernels/kv_quant.py`` — the scatter quantizes under monotone
    per-page-per-head scales and attention reads ``bits * scale`` in
    fp32.  Beyond this function (and the kernel/oracle it dispatches
    to) nobody sees quantized bytes.

    Two backends (``resolve_attn_backend``):

    * ``fused`` — the Pallas kernel in ``kernels/paged_attn.py``:
      in-kernel scatter + online-softmax streaming of only the pages a
      request owns; the pools are updated in place (aliased).  HBM
      traffic scales with live context, not block-table width.
    * ``gather`` — the differential oracle: scatter (tokens with
      ``write_mask`` False — padding of a partial chunk, inactive
      decode slots — are redirected to the trash page), then gather the
      full per-request page view and run the same masked softmax as the
      contiguous path.  Attends every ``block_tables.shape[1]`` pages,
      so callers (the server) should narrow the table width to the
      tick's live context rather than always passing
      ``max_pages_per_request``.

    The two agree to fp32 rounding on every row a reader observes; rows
    of inactive slots (no pages allocated) are garbage on both paths
    (uniform-softmax garbage vs zeros) and are never read.
    """
    from repro.kernels import kv_quant

    B, S, D = x.shape
    page = pool["k"].shape[2]
    trash = pool["k"].shape[0] - 1
    quantized = kv_quant.is_quantized(kv_dtype)
    if quantized:
        assert "k_scale" in pool, (
            f"kv_dtype={kv_dtype!r} needs scale pools; build the pool "
            "with paged_cache_specs(..., kv_dtype=...)"
        )
    positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B,S]
    q, k_new, v_new = _project_qkv(params, x, positions, cfg, use_rope=True)

    if backend == "fused":
        from repro.kernels import ops

        window = cfg.sliding_window \
            if (kind == "local" and cfg.sliding_window) else 0
        out = ops.paged_attention(
            q, k_new, v_new, pool["k"], pool["v"], block_tables, pos,
            write_mask, scale_k=pool.get("k_scale"),
            scale_v=pool.get("v_scale"), kv_dtype=kv_dtype, window=window,
        )
        y = _out_proj(params, out[0].astype(x.dtype), cfg)
        new_pool = {"k": out[1], "v": out[2]}
        if quantized:
            new_pool["k_scale"], new_pool["v_scale"] = out[3], out[4]
        return y, new_pool

    logical_page = positions // page
    offset = positions % page
    gp = jnp.take_along_axis(
        block_tables, jnp.clip(logical_page, 0, block_tables.shape[1] - 1), axis=1
    )  # [B, S] pool page per new token
    ok = write_mask & (gp >= 0) & (logical_page < block_tables.shape[1])
    gp = jnp.where(ok, gp, trash)
    KV, hd = k_new.shape[2], k_new.shape[3]
    gpf, off = gp.reshape(-1), offset.reshape(-1)
    if quantized:
        nk, nsk = kv_quant.quantize_scatter_ref(
            pool["k"], pool["k_scale"], gpf, off,
            k_new.reshape(B * S, KV, hd), kv_dtype,
        )
        nv, nsv = kv_quant.quantize_scatter_ref(
            pool["v"], pool["v_scale"], gpf, off,
            v_new.reshape(B * S, KV, hd), kv_dtype,
        )
        new_pool = {"k": nk, "v": nv, "k_scale": nsk, "v_scale": nsv}
    else:
        new_pool = {
            "k": pool["k"].at[gpf, :, off].set(
                k_new.reshape(B * S, KV, hd).astype(pool["k"].dtype)
            ),
            "v": pool["v"].at[gpf, :, off].set(
                v_new.reshape(B * S, KV, hd).astype(pool["v"].dtype)
            ),
        }

    k_cache = _gather_pages(new_pool["k"], block_tables)  # [B, C, KV, hd]
    v_cache = _gather_pages(new_pool["v"], block_tables)
    if quantized:
        k_cache = kv_quant.dequantize(
            k_cache, kv_quant.gather_scales(new_pool["k_scale"],
                                            block_tables, page)
        )
        v_cache = kv_quant.dequantize(
            v_cache, kv_quant.gather_scales(new_pool["v_scale"],
                                            block_tables, page)
        )
    else:
        # attention math always in fp32 (no-op for fp32 pools; bf16
        # pools round on write, upcast on read — matches the kernel)
        k_cache = k_cache.astype(jnp.float32)
        v_cache = v_cache.astype(jnp.float32)
    C = k_cache.shape[1]

    H = cfg.num_heads
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, KV, G, hd)
    kpos = jnp.arange(C, dtype=jnp.int32)[None, None, :]  # [1,1,C]
    qpos = positions[:, :, None]  # [B,S,1]
    valid = kpos <= qpos
    if kind == "local" and cfg.sliding_window:
        valid &= kpos > qpos - cfg.sliding_window
    # pages never allocated hold stale/zero data — mask them out
    page_alloc = (block_tables >= 0)[:, :, None]  # [B, n, 1]
    valid &= page_alloc.repeat(page, axis=2).reshape(B, 1, C)
    mask = valid[:, None, None]  # [B,1,1,S,C]
    ctx = _attend(qg, k_cache, v_cache, mask, scale)
    y = _out_proj(params, ctx.reshape(B, S, H, hd), cfg)
    return y, new_pool


def attn_decode(
    params: Dict,
    cache: Dict,
    x: jax.Array,
    pos: jax.Array,
    cfg,
    kind: str = "global",
) -> Tuple[jax.Array, Dict]:
    """One decode step. x: [B,1,D]; pos: scalar int32 (tokens so far)."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, positions, cfg, use_rope=True)

    C = cache["k"].shape[1]
    is_ring = bool(kind == "local" and cfg.sliding_window and C == cfg.sliding_window)
    slot = (pos % C) if is_ring else jnp.minimum(pos, C - 1)
    int8 = cache["k"].dtype == jnp.int8
    new_cache = {}
    if int8:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        for name, val in (("k", kq), ("v", vq), ("k_scale", ks), ("v_scale", vs)):
            new_cache[name] = jax.lax.dynamic_update_slice_in_dim(
                cache[name], val, slot, axis=1
            )
        k_cache = dequantize_kv(new_cache["k"], new_cache["k_scale"], x.dtype)
        v_cache = dequantize_kv(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)
        new_cache = {"k": k_cache, "v": v_cache}

    KV, hd = cfg.num_kv_heads, cfg.head_dim
    H = cfg.num_heads
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, 1, KV, G, hd)
    scores = _grouped_scores(qg, k_cache) * scale  # [B,KV,G,1,C]

    slots_idx = jnp.arange(C)
    if is_ring:
        # ring slot s holds global position: the latest p <= pos with p%C==s
        n_valid = jnp.minimum(pos + 1, C)
        age = (pos - slots_idx) % C  # 0 = newest
        valid = age < n_valid
    else:
        valid = slots_idx <= pos
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v_cache.dtype), v_cache)
    y = _out_proj(params, ctx.reshape(B, 1, H, hd), cfg)
    return y, new_cache
