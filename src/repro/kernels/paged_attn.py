"""Fused paged-attention decode kernel (TPU-native, FlashAttention-style
online softmax over KV pages).

This is the serving decode path's answer to the gather-then-attend
oracle in ``models/layers/attention.py::paged_attn_step``: instead of
materializing every request's full contiguous KV view
(``[B, W*page, KV, hd]`` per layer, per token) and masking dead
positions, one kernel

* **scatters** the step's new K/V rows into their pages in-kernel (the
  page pools are aliased as input *and* output, so XLA updates them in
  place — no pool copy per tick),
* **streams only owned pages**: the grid is ``(B, KV, W)`` but each
  request attends ``num_pages[b] = min(ceil((pos[b]+S)/page),
  allocated[b])`` pages; tail steps clamp their block-table lookup to
  the last owned page (a repeated BlockSpec index elides the DMA) and
  ``@pl.when`` skips their compute, so HBM reads scale with the *live*
  context, not ``max_len``,
* accumulates the softmax **online** per page block (running row max
  ``m``, running normalizer ``l``, unnormalized accumulator ``acc`` in
  VMEM scratch; DESIGN.md section 10 gives the recurrence),
* handles GQA (``G = H/KV`` query rows folded per KV head), per-request
  causal offsets (query ``s`` sits at absolute position ``pos[b]+s``),
  and the ``local`` sliding-window kind (window mask + whole-page skip
  below the window),
* serves ``S = 1`` vanilla decode, ``S = spec_k+1`` speculative-verify
  rows, and ``S = chunk`` prefill chunks with one kernel body,
* optionally holds the pools **quantized** (``kv_dtype`` int8/fp8,
  DESIGN.md section 15): a parallel per-page-per-head fp32 scale pool
  rides the same block table; the scatter updates each touched page's
  scale monotonically (``max(old, absmax(new rows)/qmax)``),
  re-encodes the page under it, and the online-softmax loop reads
  ``bits * scale`` in fp32 — quantized bytes never leave the kernel.

Contract (the serving block tables satisfy both by construction):

* tables are **prefix-allocated** — non-negative page ids form a prefix
  of each row (the kernel derives the owned-page count from them);
* a page being written this step (positions ``[pos, pos+S)`` with
  ``write_mask`` set) is **exclusively owned** by its request (the
  scheduler's copy-on-write contract, ``serving/paged.py``) — shared
  prefix pages are read-only here, so the in-place scatter never races
  a reader.  Read-only pages are rewritten with their own bits (and,
  quantized, their own scale: no new rows -> the monotone update is a
  no-op and the re-encode is exact), which keeps the unconditional
  block write-back benign.

Masked rows (``write_mask`` False: padded chunk tokens, inactive decode
slots, draft positions past a request's ``k_r``) are simply *not
written* — unlike the oracle, nothing is redirected to the trash page,
so the trash page's contents may differ between the two paths (never
observable: no reader ever attends it).

Differential fuzz vs the oracle: ``tests/test_paged_attn_kernel.py``
(fp32/bf16) and ``tests/test_kv_quant.py`` (int8/fp8 + error budget).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import kv_quant
from repro.kernels.backend import resolve_interpret

NEG_INF = -2.0e38  # large finite negative (matches attention.py)


def _absmax11(x: jax.Array) -> jax.Array:
    """max |x| over a 2-D block as a [1, 1] vector (max is exact in any
    order, so this equals the oracle's flat ``jnp.max``)."""
    a = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    return jnp.max(a, axis=0, keepdims=True)


def _kernel(
    # scalar prefetch
    bt_ref,    # [B, W] int32 page ids (-1 = unallocated)
    pos_ref,   # [B] int32 tokens already cached
    np_ref,    # [B] int32 owned pages this step attends
    # tensor inputs (quantized adds sk/sv scale blocks), then outputs
    # (quantized adds osk/osv), then scratch — unpacked below:
    #   q_ref   [1, 1, S*G, hd] queries of (b, kv)
    #   kn_ref  [1, 1, S, hd] new keys of (b, kv)
    #   vn_ref  [1, 1, S, hd] new values of (b, kv)
    #   wm_ref  [1, 1, S] int32 write mask of b
    #   pk_ref  [1, 1, page, hd] key page of head kv (pre-scatter bits)
    #   pv_ref  [1, 1, page, hd] value page of head kv
    #   sk_ref  [1, 1, 1, 1] fp32 key-page scale        (quantized only)
    #   sv_ref  [1, 1, 1, 1] fp32 value-page scale      (quantized only)
    #   ctx_ref [1, 1, S*G, hd] fp32 attention output of (b, kv)
    #   opk_ref [1, 1, page, hd] updated key page (aliases pk)
    #   opv_ref [1, 1, page, hd] updated value page (aliases pv)
    #   osk_ref [1, 1, 1, 1] updated key scale (aliases sk, quantized)
    #   osv_ref [1, 1, 1, 1] updated value scale (aliases sv, quantized)
    #   m_ref   [S*G, 128] fp32 running row max
    #   l_ref   [S*G, 128] fp32 running normalizer
    #   acc_ref [S*G, hd] fp32 unnormalized context accumulator
    *refs,
    page: int,
    S: int,
    G: int,
    window: int,
    scale: float,
    kv_dtype: str,
):
    quantized = kv_quant.is_quantized(kv_dtype)
    if quantized:
        (q_ref, kn_ref, vn_ref, wm_ref, pk_ref, pv_ref, sk_ref, sv_ref,
         ctx_ref, opk_ref, opv_ref, osk_ref, osv_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, kn_ref, vn_ref, wm_ref, pk_ref, pv_ref,
         ctx_ref, opk_ref, opv_ref, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    j = pl.program_id(2)
    W = pl.num_programs(2)
    posb = pos_ref[b]
    npb = np_ref[b]
    SG = acc_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The logical page this step actually loaded: tail steps (j >= npb)
    # clamp to the last owned page — same BlockSpec index as the step
    # before, so no new DMA — and recompute its bits idempotently (the
    # output block must be rewritten every step or the final flush of
    # the clamped page would revert the scatter).
    j_eff = jnp.maximum(jnp.minimum(j, npb - 1), 0)

    # -- scatter: new K/V rows whose position lands in this page ----------
    # one-hot [page, S] matmul scatter: slot p takes new row s iff the
    # slot's absolute position equals pos+s and s is really written —
    # at most one s matches per slot, so the contraction reproduces the
    # row bits exactly (a single 1.0 multiply)
    k_page = pk_ref[0, 0]  # [page, hd]
    v_page = pv_ref[0, 0]
    kpos_col = j_eff * page + jax.lax.broadcasted_iota(
        jnp.int32, (page, 1), 0
    )  # [page, 1] absolute position of each slot
    new_pos = posb + jax.lax.broadcasted_iota(jnp.int32, (page, S), 1)
    onehot = (kpos_col == new_pos) & (wm_ref[0] > 0)
    oh = onehot.astype(jnp.float32)
    hit = jnp.max(oh, axis=1, keepdims=True) > 0  # [page, 1]
    k_scat = jax.lax.dot_general(
        oh, kn_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    v_scat = jax.lax.dot_general(
        oh, vn_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if quantized:
        # page-boundary quantization (kernels/kv_quant.py): grow the
        # page's scale over the rows landing here (monotone — old rows
        # re-encode by dividing by a larger scale, never clipping),
        # then re-encode the whole page under it.  No new rows ->
        # s_new == s_old and the re-encode restores the old bits
        # exactly, so the unconditional write-back stays benign for
        # shared (read-only) pages and clamped tail steps.  Scales stay
        # [1, 1] vectors: VMEM holds no scalars.
        sk_old = sk_ref[0, 0]
        sv_old = sv_ref[0, 0]
        sk_new = kv_quant.new_scale(sk_old, _absmax11(k_scat), kv_dtype)
        sv_new = kv_quant.new_scale(sv_old, _absmax11(v_scat), kv_dtype)
        sk_eff = jnp.maximum(sk_new, kv_quant.EPS)
        sv_eff = jnp.maximum(sv_new, kv_quant.EPS)
        k_f = jnp.where(hit, k_scat, k_page.astype(jnp.float32) * sk_old)
        v_f = jnp.where(hit, v_scat, v_page.astype(jnp.float32) * sv_old)
        k_page = kv_quant.quantize(k_f, sk_eff, kv_dtype)
        v_page = kv_quant.quantize(v_f, sv_eff, kv_dtype)
        osk_ref[0, 0] = sk_new
        osv_ref[0, 0] = sv_new
        k_att = k_page.astype(jnp.float32) * sk_new
        v_att = v_page.astype(jnp.float32) * sv_new
    else:
        k_page = jnp.where(hit, k_scat.astype(k_page.dtype), k_page)
        v_page = jnp.where(hit, v_scat.astype(v_page.dtype), v_page)
        # attention math always in fp32 (no-op for fp32 pools; bf16
        # pools round on write, upcast on read)
        k_att = k_page.astype(jnp.float32)
        v_att = v_page.astype(jnp.float32)
    opk_ref[0, 0] = k_page
    opv_ref[0, 0] = v_page

    # -- online-softmax accumulation over owned pages ---------------------
    attend = j < npb
    if window:
        # whole pages below every query's window contribute nothing
        attend &= (j_eff * page + page - 1) > (posb - window)

    @pl.when(attend)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)  # [SG, hd]
        s_mat = jax.lax.dot_general(
            q, k_att, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [SG, page] fp32
        qpos = posb + jax.lax.broadcasted_iota(
            jnp.int32, (SG, page), 0
        ) // G
        kpos = j_eff * page + jax.lax.broadcasted_iota(
            jnp.int32, (SG, page), 1
        )
        valid = kpos <= qpos
        if window:
            valid &= kpos > qpos - window
        s_mat = jnp.where(valid, s_mat, NEG_INF)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s_mat, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit where: a fully-masked row keeps m == NEG_INF (finite),
        # and exp(NEG_INF - NEG_INF) == 1 must not count as weight
        p = jnp.where(valid, jnp.exp(s_mat - m_new), 0.0)
        l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_att, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == W - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        ctx_ref[0, 0] = jnp.where(l > 0, acc_ref[...] / l, 0.0)


@functools.partial(
    jax.jit, static_argnames=("window", "kv_dtype", "interpret")
)
def paged_attn(
    q: jax.Array,            # [B, S, H, hd] (rope applied)
    k_new: jax.Array,        # [B, S, KV, hd]
    v_new: jax.Array,        # [B, S, KV, hd]
    pool_k: jax.Array,       # [P+1, KV, page, hd]
    pool_v: jax.Array,       # [P+1, KV, page, hd]
    block_tables: jax.Array, # [B, W] int32 page ids, -1 = unallocated
    pos: jax.Array,          # [B] int32 tokens already cached
    write_mask: jax.Array,   # [B, S] bool
    *,
    scale_k: Optional[jax.Array] = None,  # [P+1, KV, 1, 1] fp32
    scale_v: Optional[jax.Array] = None,
    kv_dtype: str = "fp32",
    window: int = 0,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, ...]:
    """Fused scatter + paged attention.  Returns
    ``(ctx [B,S,H,hd] fp32, new_pool_k, new_pool_v)`` — plus
    ``(new_scale_k, new_scale_v)`` for quantized ``kv_dtype`` — with
    the pools (and scale pools) updated in place (input/output
    aliased)."""
    B, S, H, hd = q.shape
    KV = k_new.shape[2]
    assert H % KV == 0, (H, KV)
    G = H // KV
    SG = S * G
    page = pool_k.shape[2]
    W = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    quantized = kv_quant.is_quantized(kv_dtype)
    if quantized:
        assert scale_k is not None and scale_v is not None, kv_dtype

    # fold GQA groups next to their KV head: row s*G + g of (b, kv)
    qf = q.reshape(B, S, KV, G, hd).transpose(0, 2, 1, 3, 4)
    qf = qf.reshape(B, KV, SG, hd)
    knt = k_new.transpose(0, 2, 1, 3)  # [B, KV, S, hd]
    vnt = v_new.transpose(0, 2, 1, 3)

    bt = block_tables.astype(jnp.int32)
    n_alloc = jnp.sum((bt >= 0).astype(jnp.int32), axis=1)
    num_pages = jnp.minimum(
        (pos.astype(jnp.int32) + S + page - 1) // page, n_alloc
    )
    wm = write_mask.astype(jnp.int32)[:, None, :]  # [B, 1, S]
    trash = pool_k.shape[0] - 1

    def page_idx(b, kv, j, bt, pos, np_):
        # tail steps repeat the last owned page id -> DMA elided; rows
        # with nothing allocated map to the trash page (never read —
        # mapping them to a real page would race its owner's scatter
        # when the unconditional block write-back flushes stale bits)
        last = jnp.maximum(jnp.minimum(j, np_[b] - 1), 0)
        p = bt[b, last]
        return (jnp.where(p < 0, trash, p), kv, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, SG, hd),
                     lambda b, kv, j, *_: (b, kv, 0, 0)),
        pl.BlockSpec((1, 1, S, hd),
                     lambda b, kv, j, *_: (b, kv, 0, 0)),
        pl.BlockSpec((1, 1, S, hd),
                     lambda b, kv, j, *_: (b, kv, 0, 0)),
        pl.BlockSpec((1, 1, S), lambda b, kv, j, *_: (b, 0, 0)),
        pl.BlockSpec((1, 1, page, hd), page_idx),
        pl.BlockSpec((1, 1, page, hd), page_idx),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, SG, hd),
                     lambda b, kv, j, *_: (b, kv, 0, 0)),
        pl.BlockSpec((1, 1, page, hd), page_idx),
        pl.BlockSpec((1, 1, page, hd), page_idx),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, KV, SG, hd), jnp.float32),
        jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
        jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype),
    ]
    operands = [bt, pos.astype(jnp.int32), num_pages, qf, knt, vnt, wm,
                pool_k, pool_v]
    # pool_k/pool_v are operands 7/8 (scalar-prefetch args count);
    # quantized runs alias the scale pools right behind them
    aliases = {7: 1, 8: 2}
    if quantized:
        # scale-pool blocks ride the same page_idx map as their pages
        in_specs += [pl.BlockSpec((1, 1, 1, 1), page_idx)] * 2
        out_specs += [pl.BlockSpec((1, 1, 1, 1), page_idx)] * 2
        out_shape += [
            jax.ShapeDtypeStruct(scale_k.shape, scale_k.dtype),
            jax.ShapeDtypeStruct(scale_v.shape, scale_v.dtype),
        ]
        operands += [scale_k, scale_v]
        aliases = {7: 1, 8: 2, 9: 3, 10: 4}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, W),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((SG, 128), jnp.float32),
            pltpu.VMEM((SG, 128), jnp.float32),
            pltpu.VMEM((SG, hd), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(
            _kernel, page=page, S=S, G=G, window=window, scale=scale,
            kv_dtype=kv_dtype,
        ),
        grid_spec=grid_spec,
        out_shape=tuple(out_shape),
        input_output_aliases=aliases,
        interpret=resolve_interpret(interpret),
        # the HLO instruction name the benchmark's trace reader matches
        name="paged_attn",
    )(*operands)
    ctx = outs[0].reshape(B, KV, S, G, hd).transpose(0, 2, 1, 3, 4)
    return (ctx.reshape(B, S, H, hd),) + tuple(outs[1:])
