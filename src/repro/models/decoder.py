"""Unified model assembly for every architecture family.

The layer stack is planned into **segments**:

* ``scan`` segments — homogeneous (or pattern-periodic) runs of layers
  whose params are stacked on a leading ``layers`` axis and executed
  with ``jax.lax.scan`` (keeps HLO size O(period), essential for 48-64
  layer stacks compiled for 512 devices);
* ``unroll`` segments — shape-heterogeneous leftovers (leading dense
  layers of MoE stacks, pattern remainders).

Pattern-periodic stacks (gemma3 5:1 local:global, recurrentgemma
rec-rec-attn) scan over *periods*, with per-position params stacked
separately, so each position keeps a static layer kind (no traced
branching, no wasted FLOPs).

Public API (all pure, jit-friendly; ``cfg`` static):

    build_plan(cfg)                          -> SegmentPlan
    model_specs(cfg)                         -> ParamSpec tree
    init_params(cfg, rng)                    -> params
    forward(params, cfg, tokens, ...)        -> logits, Aux
    loss_fn(params, batch, cfg)              -> loss, metrics
    cache_specs(cfg, batch, max_len)         -> ParamSpec tree (decode cache)
    fill_cache_from_prefill(cfg, cache, aux) -> cache
    decode_step(params, cfg, cache, token, pos, pruned=None) -> logits, cache
    decode_step_paged(params, cfg, pools, bt, tokens, pos, ...) -> logits, pools, stats
    verify_step_paged(params, cfg, pools, bt, tokens, pos, mask) -> logits, pools
    copy_pool_pages(cfg, pools, src, dst)    -> pools (COW page forks)
    extract_ffn_tree(params, cfg)            -> tree of dense-FF params
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain, psum_if_tp
from repro.models import param as param_lib
from repro.models.common import (
    apply_norm,
    embed_lookup,
    embed_specs,
    head_specs,
    lm_logits,
    norm_specs,
)
from repro.models.layers import attention as attn_lib
from repro.models.layers import ffn as ffn_lib
from repro.models.layers import mla as mla_lib
from repro.models.layers import moe as moe_lib
from repro.models.layers import rglru as rglru_lib
from repro.models.layers import ssm as ssm_lib
from repro.models.param import ParamSpec


# ---------------------------------------------------------------------------
# Stack planning
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LayerDesc:
    mixer: str  # "attn" | "ssm" | "rec"
    attn_kind: str  # "global" | "local" | ""
    ffn: str  # "dense" | "moe" | "none"


@dataclass(frozen=True)
class Segment:
    kind: str  # "scan" | "unroll"
    descs: Tuple[LayerDesc, ...]  # per position (scan period) or per layer
    n: int  # number of periods (scan) / layers (unroll == len(descs))


def layer_descs(cfg) -> List[LayerDesc]:
    descs = []
    for li in range(cfg.num_layers):
        mixer = cfg.layer_mixer_kind(li)
        akind = cfg.attn_kind(li) if mixer == "attn" else ""
        if cfg.num_experts and li >= cfg.num_dense_layers:
            f = "moe"
        elif cfg.d_ff > 0:
            f = "dense"
        else:
            f = "none"
        descs.append(LayerDesc(mixer, akind, f))
    return descs


def build_plan(cfg) -> List[Segment]:
    descs = layer_descs(cfg)
    L = cfg.num_layers
    start = cfg.num_dense_layers if cfg.num_experts else 0
    p = max(len(cfg.attn_pattern), 1)
    if cfg.block_pattern:
        p = max(p, len(cfg.block_pattern))
    segments: List[Segment] = []
    if start:
        segments.append(Segment("unroll", tuple(descs[:start]), start))
    n_scan = (L - start) // p
    if n_scan > 0:
        segments.append(Segment("scan", tuple(descs[start : start + p]), n_scan))
    rem = descs[start + n_scan * p :]
    if rem:
        segments.append(Segment("unroll", tuple(rem), len(rem)))
    return segments


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def _mixer_specs(cfg, desc: LayerDesc):
    if desc.mixer == "attn":
        return mla_lib.mla_specs(cfg) if cfg.use_mla else attn_lib.attn_specs(cfg)
    if desc.mixer == "ssm":
        return ssm_lib.ssm_specs(cfg)
    return rglru_lib.rglru_specs(cfg)


def layer_specs(cfg, desc: LayerDesc) -> Dict:
    s: Dict[str, Any] = {
        "mixer_norm": norm_specs(cfg),
        "mixer": _mixer_specs(cfg, desc),
    }
    if desc.ffn == "dense":
        s["ffn_norm"] = norm_specs(cfg)
        s["ffn"] = ffn_lib.ffn_specs(cfg)
    elif desc.ffn == "moe":
        s["ffn_norm"] = norm_specs(cfg)
        s["ffn"] = moe_lib.moe_specs(cfg)
    return s


def model_specs(cfg) -> Dict:
    specs: Dict[str, Any] = {"embed": embed_specs(cfg)}
    hs = head_specs(cfg)
    if hs:
        specs["head"] = hs
    if cfg.frontend:
        specs["frontend"] = {
            "proj": ParamSpec((cfg.d_model, cfg.d_model), ("embed", "act_embed"))
        }
    for i, seg in enumerate(build_plan(cfg)):
        if seg.kind == "scan":
            specs[f"seg{i}"] = {
                f"pos{j}": param_lib.stack_specs(layer_specs(cfg, d), seg.n)
                for j, d in enumerate(seg.descs)
            }
        else:
            specs[f"seg{i}"] = {
                f"layer{j}": layer_specs(cfg, d) for j, d in enumerate(seg.descs)
            }
    specs["final_norm"] = norm_specs(cfg)
    if cfg.mtp_depth:
        # DeepSeek-style MTP module: shared embed/head, 1 extra block
        mtp_desc = build_plan(cfg)[-1].descs[-1]
        specs["mtp"] = {
            "norm_h": norm_specs(cfg),
            "norm_e": norm_specs(cfg),
            "proj": ParamSpec((2 * cfg.d_model, cfg.d_model), (None, "embed")),
            "layer": layer_specs(cfg, mtp_desc),
        }
    return specs


def init_params(cfg, rng: jax.Array) -> Dict:
    return param_lib.init_params(model_specs(cfg), rng, cfg.dtype)


def abstract_params(cfg) -> Dict:
    return param_lib.abstract_params(model_specs(cfg), cfg.dtype)


# ---------------------------------------------------------------------------
# Aux containers
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass
class Aux:
    """Per-forward side outputs, trees mirroring the segment structure."""
    kv: Any = None  # raw per-layer cache material (prefill)
    stats: Any = None  # GRIFFIN s_sq leaves [.., B, F]
    moe_aux: Any = 0.0
    x_norms: Any = None  # FF input norms (Adaptive Wanda baseline)
    z_norms: Any = None


# ---------------------------------------------------------------------------
# Single-layer application (full sequence)
# ---------------------------------------------------------------------------

def _apply_layer(
    lp: Dict,
    desc: LayerDesc,
    x: jax.Array,
    positions: jax.Array,
    cfg,
    collect_stats: bool,
    q_chunk: int,
    pruned_ffn: Optional[Dict] = None,
    want_z: bool = False,
):
    h = apply_norm(lp["mixer_norm"], x, cfg)
    if desc.mixer == "attn":
        if cfg.use_mla:
            y, kv = mla_lib.mla_forward(lp["mixer"], h, positions, cfg, q_chunk)
            kv = {"ckv": kv[0], "kr": kv[1]}
        else:
            y, (k, v) = attn_lib.attn_forward(
                lp["mixer"], h, positions, cfg, kind=desc.attn_kind, q_chunk=q_chunk
            )
            kv = {"k": k, "v": v}
    elif desc.mixer == "ssm":
        y, kv = ssm_lib.ssm_forward(lp["mixer"], h, cfg)
    else:
        y, kv = rglru_lib.rglru_forward(lp["mixer"], h, cfg)
    x = x + y
    x = constrain(x, ("batch", "seq", "act_embed"))

    stats = None
    aux = jnp.zeros((), jnp.float32)
    if desc.ffn != "none":
        h = apply_norm(lp["ffn_norm"], x, cfg)
        if desc.ffn == "dense":
            fp = pruned_ffn if pruned_ffn is not None else lp["ffn"]
            y, stats = ffn_lib.ffn_forward(fp, h, cfg, collect_stats, want_z)
        elif pruned_ffn is not None:
            y = moe_lib.moe_decode(lp["ffn"], pruned_ffn, h, cfg)
        else:
            y, aux, stats = moe_lib.moe_forward(
                lp["ffn"], h, cfg, collect_stats=collect_stats, want_z=want_z
            )
        x = x + y
        x = constrain(x, ("batch", "seq", "act_embed"))
    if stats is None:  # uniform pytree shape across scan positions
        B, S = x.shape[0], x.shape[1]
        stats = {
            "s_sq": jnp.zeros((B, 0), jnp.float32),
            "x_sq": jnp.zeros((0,), jnp.float32),
            "z_sq": jnp.zeros((0,), jnp.float32),
        }
        if want_z:
            stats["z"] = jnp.zeros((B, S, 0), x.dtype)
    return x, kv, stats, aux


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill)
# ---------------------------------------------------------------------------

def forward(
    params: Dict,
    cfg,
    tokens: Optional[jax.Array] = None,
    prefix_emb: Optional[jax.Array] = None,
    *,
    collect_stats: bool = False,
    want_kv: bool = False,
    q_chunk: int = 1024,
    remat: Optional[bool] = None,
    logits_mode: str = "all",  # "all" | "last" | "none" (hidden states)
    pruned: Optional[Dict] = None,
    want_z: bool = False,
) -> Tuple[jax.Array, Aux]:
    """Full-sequence forward.

    ``logits_mode="last"`` projects only the final position (prefill:
    avoids a [B,S,V] tensor); ``"none"`` returns hidden states (train
    loss uses chunked CE instead).  ``pruned``: GRIFFIN-compacted FF
    tree — runs the *generation-phase* model over a full (teacher-
    forced) sequence, used by the paper's evaluation protocol.
    """
    parts = []
    if prefix_emb is not None:
        pe = prefix_emb
        if "frontend" in params:
            pe = jnp.einsum("bpd,de->bpe", pe, params["frontend"]["proj"])
        parts.append(pe.astype(jnp.dtype(cfg.dtype)))
    if tokens is not None:
        parts.append(embed_lookup(params["embed"], tokens, cfg))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = constrain(x, ("batch", "seq", "act_embed"))

    use_remat = cfg.remat if remat is None else remat
    plan = build_plan(cfg)
    kv_tree: Dict[str, Any] = {}
    stats_tree: Dict[str, Any] = {}
    moe_aux = jnp.zeros((), jnp.float32)

    for i, seg in enumerate(plan):
        sp = params[f"seg{i}"]
        seg_pruned = (pruned or {}).get(f"seg{i}")
        if seg.kind == "unroll":
            kv_seg, st_seg = {}, {}
            for j, desc in enumerate(seg.descs):
                pf = (seg_pruned or {}).get(f"layer{j}")
                x, kv, s_sq, aux = _apply_layer(
                    sp[f"layer{j}"], desc, x, positions, cfg, collect_stats,
                    q_chunk, pf, want_z,
                )
                moe_aux = moe_aux + aux
                if want_kv:
                    kv_seg[f"layer{j}"] = kv
                if collect_stats:
                    st_seg[f"layer{j}"] = s_sq
            kv_tree[f"seg{i}"] = kv_seg
            stats_tree[f"seg{i}"] = st_seg
        else:
            def body(carry, xs, _descs=seg.descs,
                     _has_pruned=seg_pruned is not None):
                x_c, aux_c = carry
                lp_all, pruned_all = xs
                kv_out, st_out = {}, {}
                for j, desc in enumerate(_descs):
                    pf = pruned_all.get(f"pos{j}") if _has_pruned else None
                    x_c, kv, s_sq, aux = _apply_layer(
                        lp_all[f"pos{j}"], desc, x_c, positions, cfg,
                        collect_stats, q_chunk, pf, want_z,
                    )
                    aux_c = aux_c + aux
                    kv_out[f"pos{j}"] = kv if want_kv else {}
                    st_out[f"pos{j}"] = s_sq if collect_stats else jnp.zeros(())
                return (x_c, aux_c), (kv_out, st_out)

            if use_remat:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable
                )
            (x, moe_aux), (kv_seg, st_seg) = jax.lax.scan(
                body, (x, moe_aux), (sp, seg_pruned or {})
            )
            if want_kv:
                kv_tree[f"seg{i}"] = kv_seg
            if collect_stats:
                stats_tree[f"seg{i}"] = st_seg

    x = apply_norm(params["final_norm"], x, cfg)
    if logits_mode == "none":
        out = x
    elif logits_mode == "last":
        out = lm_logits(params.get("head", {}), params["embed"], x[:, -1:], cfg)
    else:
        out = lm_logits(params.get("head", {}), params["embed"], x, cfg)
    return out, Aux(
        kv=kv_tree if want_kv else None,
        stats=stats_tree if collect_stats else None,
        moe_aux=moe_aux,
    )


def hidden_forward(
    params: Dict, cfg, tokens=None, prefix_emb=None, *, q_chunk: int = 1024,
    remat: Optional[bool] = None,
) -> Tuple[jax.Array, Aux]:
    """Final hidden states (pre-head)."""
    return forward(
        params, cfg, tokens, prefix_emb, q_chunk=q_chunk, remat=remat,
        logits_mode="none",
    )


# ---------------------------------------------------------------------------
# Loss (chunked cross-entropy: never materializes [B,S,V] fp32 logits)
# ---------------------------------------------------------------------------

def _ce_chunk(x, params, targets, mask, cfg):
    logits = lm_logits(params.get("head", {}), params["embed"], x, cfg)  # fp32
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    return jnp.sum(nll), jnp.sum(mask)


def chunked_ce(
    x: jax.Array, params: Dict, targets: jax.Array, mask: jax.Array, cfg,
    chunk: int = 512,
) -> Tuple[jax.Array, jax.Array]:
    """x: [B,S,D]; targets/mask: [B,S]. Returns (sum nll, count)."""
    B, S, D = x.shape
    if S <= chunk:
        return _ce_chunk(x, params, targets, mask, cfg)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
        S += pad
    n = S // chunk

    def body(carry, inp):
        xs, ts, ms = inp
        nll, cnt = _ce_chunk(xs, params, ts, ms, cfg)
        return (carry[0] + nll, carry[1] + cnt), None

    (nll, cnt), _ = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (
            jnp.moveaxis(x.reshape(B, n, chunk, D), 1, 0),
            jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0),
            jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0),
        ),
    )
    return nll, cnt


def loss_fn(params: Dict, batch: Dict, cfg) -> Tuple[jax.Array, Dict]:
    """batch: {tokens [B,S], (prefix_emb), (targets), (mask)}.

    For decoder LMs, targets default to next-token shift of ``tokens``;
    encoders require explicit framewise targets.
    """
    prefix = batch.get("prefix_emb")
    if cfg.family == "encoder":
        x, aux = hidden_forward(params, cfg, prefix_emb=prefix)
        targets = batch["targets"]
        mask = batch.get("mask", jnp.ones(targets.shape, jnp.float32))
        nll, cnt = chunked_ce(x, params, targets, mask, cfg)
        loss = nll / jnp.maximum(cnt, 1.0)
        return loss, {"ce": loss}

    tokens = batch["tokens"]
    x, aux = hidden_forward(params, cfg, tokens=tokens, prefix_emb=prefix)
    P = 0 if prefix is None else prefix.shape[1]
    x_text = x[:, P:]
    targets = batch.get("targets")
    if targets is None:
        targets = tokens[:, 1:]
        x_text = x_text[:, :-1]
        mask = batch.get("mask", jnp.ones(targets.shape, jnp.float32))
        mask = mask[:, : targets.shape[1]]
    else:
        mask = batch.get("mask", jnp.ones(targets.shape, jnp.float32))
    nll, cnt = chunked_ce(x_text, params, targets, mask, cfg)
    ce = nll / jnp.maximum(cnt, 1.0)
    loss = ce + cfg.router_aux_coef * aux.moe_aux

    metrics = {"ce": ce, "moe_aux": aux.moe_aux}
    if cfg.mtp_depth and "mtp" in params:
        mtp_loss = _mtp_loss(params, x, tokens, cfg)
        loss = loss + 0.1 * mtp_loss
        metrics["mtp"] = mtp_loss
    metrics["loss"] = loss
    return loss, metrics


def _mtp_loss(params: Dict, h: jax.Array, tokens: jax.Array, cfg) -> jax.Array:
    """DeepSeek-V3 multi-token prediction: one extra block predicts t+2."""
    mp = params["mtp"]
    B, S, D = h.shape
    # combine hidden state at t with embedding of token t+1
    h_in = apply_norm(mp["norm_h"], h[:, : S - 2], cfg)
    e_in = apply_norm(mp["norm_e"], embed_lookup(params["embed"], tokens[:, 1 : S - 1], cfg), cfg)
    x = jnp.einsum("bsd,dk->bsk", jnp.concatenate([h_in, e_in], -1), mp["proj"])
    positions = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2])
    desc = build_plan(cfg)[-1].descs[-1]
    x, _, _, _ = _apply_layer(mp["layer"], desc, x, positions, cfg, False, 1024)
    x = apply_norm(params["final_norm"], x, cfg)
    targets = tokens[:, 2:]
    mask = jnp.ones(targets.shape, jnp.float32)
    nll, cnt = chunked_ce(x, params, targets, mask, cfg)
    return nll / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

def _layer_cache_specs(cfg, desc: LayerDesc, batch: int, max_len: int) -> Dict:
    if desc.mixer == "attn":
        if cfg.use_mla:
            return mla_lib.mla_cache_specs(cfg, batch, max_len)
        return attn_lib.init_cache_specs(cfg, desc.attn_kind, batch, max_len)
    if desc.mixer == "ssm":
        return ssm_lib.ssm_cache_specs(cfg, batch)
    return rglru_lib.rglru_cache_specs(cfg, batch)


def cache_specs(cfg, batch: int, max_len: int) -> Dict:
    tree: Dict[str, Any] = {}
    for i, seg in enumerate(build_plan(cfg)):
        if seg.kind == "scan":
            tree[f"seg{i}"] = {
                f"pos{j}": param_lib.stack_specs(
                    _layer_cache_specs(cfg, d, batch, max_len), seg.n
                )
                for j, d in enumerate(seg.descs)
            }
        else:
            tree[f"seg{i}"] = {
                f"layer{j}": _layer_cache_specs(cfg, d, batch, max_len)
                for j, d in enumerate(seg.descs)
            }
    return tree


def init_cache(cfg, batch: int, max_len: int) -> Dict:
    return param_lib.init_params(
        cache_specs(cfg, batch, max_len), jax.random.PRNGKey(0), cfg.dtype
    )


def fill_cache_from_prefill(cfg, cache: Dict, kv_tree: Dict) -> Dict:
    """Scatter prefill K/V (and states) into decode cache buffers."""

    def fill_one(desc: LayerDesc, cbuf: Dict, kv: Dict) -> Dict:
        if desc.mixer == "attn":
            if cfg.use_mla:
                return mla_lib.mla_fill_cache(cbuf, kv["ckv"], kv["kr"])
            return attn_lib.fill_cache(cbuf, kv["k"], kv["v"])
        # ssm / rec: states transfer directly
        return jax.tree.map(lambda dst, src: src.astype(dst.dtype), cbuf, kv)

    out: Dict[str, Any] = {}
    for i, seg in enumerate(build_plan(cfg)):
        key = f"seg{i}"
        seg_out = {}
        for j, desc in enumerate(seg.descs):
            if seg.kind == "scan":
                seg_out[f"pos{j}"] = jax.vmap(
                    lambda c, k, d=desc: fill_one(d, c, k)
                )(cache[key][f"pos{j}"], kv_tree[key][f"pos{j}"])
            else:
                seg_out[f"layer{j}"] = fill_one(
                    desc, cache[key][f"layer{j}"], kv_tree[key][f"layer{j}"]
                )
        out[key] = seg_out
    return out


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------

def _apply_layer_decode(
    lp: Dict,
    desc: LayerDesc,
    cache: Dict,
    x: jax.Array,
    pos: jax.Array,
    cfg,
    pruned_ffn: Optional[Dict],
):
    h = apply_norm(lp["mixer_norm"], x, cfg)
    if desc.mixer == "attn":
        if cfg.use_mla:
            y, new_cache = mla_lib.mla_decode(lp["mixer"], cache, h, pos, cfg)
        else:
            y, new_cache = attn_lib.attn_decode(
                lp["mixer"], cache, h, pos, cfg, kind=desc.attn_kind
            )
    elif desc.mixer == "ssm":
        y, new_cache = ssm_lib.ssm_decode(lp["mixer"], cache, h, cfg)
    else:
        y, new_cache = rglru_lib.rglru_decode(lp["mixer"], cache, h, cfg)
    x = x + y

    if desc.ffn != "none":
        h = apply_norm(lp["ffn_norm"], x, cfg)
        if desc.ffn == "dense":
            fp = pruned_ffn if pruned_ffn is not None else lp["ffn"]
            y, _ = ffn_lib.ffn_forward(fp, h, cfg)
        else:
            y = moe_lib.moe_decode(lp["ffn"], pruned_ffn, h, cfg)
        x = x + y
    return x, new_cache


def decode_step(
    params: Dict,
    cfg,
    cache: Dict,
    token: jax.Array,
    pos: jax.Array,
    pruned: Optional[Dict] = None,
) -> Tuple[jax.Array, Dict]:
    """One generation step. token: [B,1] int32; pos: scalar int32.

    ``pruned``: optional GRIFFIN-compacted FF tree (see
    ``extract_ffn_tree`` / ``repro.core.griffin.compact_tree``); when
    given, dense FF blocks (and MoE shared experts) use the expert
    neurons only — the paper's generation phase.
    """
    x = embed_lookup(params["embed"], token, cfg)
    x = constrain(x, ("batch", "seq", "act_embed"))
    new_cache: Dict[str, Any] = {}
    for i, seg in enumerate(build_plan(cfg)):
        key = f"seg{i}"
        sp = params[key]
        seg_cache = cache[key]
        seg_pruned = (pruned or {}).get(key)
        if seg.kind == "unroll":
            nc = {}
            for j, desc in enumerate(seg.descs):
                pf = (seg_pruned or {}).get(f"layer{j}")
                x, c = _apply_layer_decode(
                    sp[f"layer{j}"], desc, seg_cache[f"layer{j}"], x, pos, cfg, pf
                )
                nc[f"layer{j}"] = c
            new_cache[key] = nc
        else:
            def body(x_c, xs, _descs=seg.descs, _has_pruned=seg_pruned is not None):
                lp_all, cache_all, pruned_all = xs
                nc_out = {}
                for j, desc in enumerate(_descs):
                    pf = pruned_all.get(f"pos{j}") if _has_pruned else None
                    x_c, c = _apply_layer_decode(
                        lp_all[f"pos{j}"], desc, cache_all[f"pos{j}"], x_c, pos,
                        cfg, pf,
                    )
                    nc_out[f"pos{j}"] = c
                return x_c, nc_out

            x, nc = jax.lax.scan(body, x, (sp, seg_cache, seg_pruned or {}))
            new_cache[key] = nc
    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params.get("head", {}), params["embed"], x, cfg)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged-KV serving path (block-table pools; see serving/paged.py)
# ---------------------------------------------------------------------------

def supports_paged(cfg) -> bool:
    """The paged path covers the GQA/MQA attention families (dense FF or
    no FF).  MLA / SSM / RG-LRU / MoE / int8-KV fall back to the
    contiguous caches."""
    return (
        all(d.mixer == "attn" for d in layer_descs(cfg))
        and not cfg.use_mla
        and not cfg.num_experts
        and not getattr(cfg, "kv_cache_int8", False)
        and not cfg.frontend
    )


def paged_pool_specs(cfg, num_pages: int, page_size: int,
                     kv_dtype: str = "fp32") -> Dict:
    """Per-layer KV page pools, mirroring the segment structure (scan
    segments stack pools on the leading layer axis like every other
    per-layer buffer).  Quantized ``kv_dtype`` adds the per-layer scale
    pools as sibling leaves (``attention.paged_cache_specs``), so COW
    copies, donation, and TP sharding carry them automatically."""
    assert supports_paged(cfg), cfg.name
    tree: Dict[str, Any] = {}
    for i, seg in enumerate(build_plan(cfg)):
        if seg.kind == "scan":
            tree[f"seg{i}"] = {
                f"pos{j}": param_lib.stack_specs(
                    attn_lib.paged_cache_specs(
                        cfg, num_pages, page_size, kv_dtype
                    ), seg.n
                )
                for j, d in enumerate(seg.descs)
            }
        else:
            tree[f"seg{i}"] = {
                f"layer{j}": attn_lib.paged_cache_specs(
                    cfg, num_pages, page_size, kv_dtype
                )
                for j, d in enumerate(seg.descs)
            }
    return tree


def init_paged_pools(cfg, num_pages: int, page_size: int,
                     kv_dtype: str = "fp32") -> Dict:
    return param_lib.init_params(
        paged_pool_specs(cfg, num_pages, page_size, kv_dtype),
        jax.random.PRNGKey(0), cfg.dtype,
    )


def copy_pool_pages(cfg, pools: Dict, src: jax.Array,
                    dst: jax.Array) -> Dict:
    """Copy whole KV pages ``src[i] -> dst[i]`` in every layer pool.

    The device half of copy-on-write: the allocator moved a writer's
    reference onto a fresh page (``BlockAllocator.cow``), and this
    copies the shared page's bits there so the writer's history stays
    bit-identical while the original page remains frozen for its other
    holders.  ``src``/``dst`` come from the scheduler's ``StepPlan.cow``
    pairs; pair order is irrelevant (dst pages are always fresh, so no
    pair reads another's dst).  Pure and jit-friendly — the server jits
    it with the pools donated so XLA can update buffers in place
    instead of copying every pool to move one page.
    """
    out: Dict[str, Any] = {}
    for i, seg in enumerate(build_plan(cfg)):
        key = f"seg{i}"
        # page axis: 0 for unrolled layers, 1 behind the stacked layer
        # axis for scan segments (same convention as every pool buffer)
        ax = 1 if seg.kind == "scan" else 0

        def cp(buf, _ax=ax):
            taken = jnp.take(buf, src, axis=_ax)
            return buf.at[dst].set(taken) if _ax == 0 \
                else buf.at[:, dst].set(taken)

        out[key] = jax.tree.map(cp, pools[key])
    return out


def _apply_layer_paged(
    lp: Dict,
    desc: LayerDesc,
    pool: Dict,
    x: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    write_mask: jax.Array,
    cfg,
    pruned_ffn: Optional[Dict],
    collect_stats: bool,
    backend: str = "gather",
    kv_dtype: str = "fp32",
):
    h = apply_norm(lp["mixer_norm"], x, cfg)
    y, new_pool = attn_lib.paged_attn_step(
        lp["mixer"], pool, block_tables, h, pos, write_mask, cfg,
        kind=desc.attn_kind, backend=backend, kv_dtype=kv_dtype,
    )
    x = x + y

    stats = None
    if desc.ffn == "dense":
        h = apply_norm(lp["ffn_norm"], x, cfg)
        if pruned_ffn is not None:
            y = ffn_lib.ffn_forward_union(pruned_ffn, h, cfg)
        else:
            z = ffn_lib.ffn_activations(lp["ffn"], h, cfg)
            if collect_stats:
                # padded chunk tokens must not pollute the statistics:
                # zeroed rows contribute exactly 0 to every reduction
                zm = z * write_mask[:, :, None].astype(z.dtype)
                zf = zm.astype(jnp.float32)
                hm = (h * write_mask[:, :, None].astype(h.dtype)).astype(
                    jnp.float32
                )
                stats = {
                    "s_sq": ffn_lib.griffin_stat_sq(zm),
                    "x_sq": jnp.sum(jnp.square(hm), axis=(0, 1)),
                    "z_sq": jnp.sum(jnp.square(zf), axis=(0, 1)),
                }
            # sharded F axis (shard_map TP) -> partial sum per shard
            y = psum_if_tp(jnp.einsum("...f,fd->...d", z, lp["ffn"]["w2"]))
            if "b2" in lp["ffn"]:
                y = y + lp["ffn"]["b2"]
        x = x + y
    if stats is None:  # uniform pytree shape across scan positions
        B = x.shape[0]
        stats = {
            "s_sq": jnp.zeros((B, 0), jnp.float32),
            "x_sq": jnp.zeros((0,), jnp.float32),
            "z_sq": jnp.zeros((0,), jnp.float32),
        }
    return x, new_pool, stats


def decode_step_paged(
    params: Dict,
    cfg,
    pools: Dict,
    block_tables: jax.Array,  # [B, n_pages] int32, -1 = unallocated
    token: jax.Array,  # [B, S] int32 (decode: S=1; prefill chunk: S=chunk)
    pos: jax.Array,  # [B] int32 tokens already cached per request
    write_mask: Optional[jax.Array] = None,  # [B, S] bool
    pruned: Optional[Dict] = None,  # union-compacted FF tree
    collect_stats: bool = False,
    backend: str = "gather",
    kv_dtype: str = "fp32",
) -> Tuple[jax.Array, Dict, Optional[Dict]]:
    """Batched paged step with per-request positions.

    Unifies chunked prefill (B=1, S=chunk, ``collect_stats`` streams the
    GRIFFIN ``s_sq`` statistic per chunk) and batched decode (S=1, one
    request per slot).  ``pruned`` runs the GRIFFIN FF blocks over the
    server's union-compacted buffers: per FF layer path, ``wg``/``w1``/
    ``w2`` [n, C, D] (``n`` the segment's scan length, 1 for an
    unrolled layer), ``mask`` [n, B, C] each row's neurons in the
    buffer, ``n_live`` [n, shards] the blocks in use
    (``ffn.ffn_forward_union``).
    ``backend`` picks the attention path per
    ``attention.resolve_attn_backend``: the fused paged-attention
    kernel or the gather-then-attend oracle (default, bit-exact vs the
    contiguous path at fp32).  ``kv_dtype`` must match how ``pools``
    was built (``init_paged_pools``) — int8/fp8 pools carry scale
    leaves that both backends update in lockstep with the pages.
    Returns (logits [B,S,V], new pools, stats tree or None).
    """
    B, S = token.shape
    if write_mask is None:
        write_mask = jnp.ones((B, S), bool)
    x = embed_lookup(params["embed"], token, cfg)
    x = constrain(x, ("batch", "seq", "act_embed"))
    new_pools: Dict[str, Any] = {}
    stats_tree: Dict[str, Any] = {}
    for i, seg in enumerate(build_plan(cfg)):
        key = f"seg{i}"
        sp = params[key]
        seg_pool = pools[key]
        seg_pruned = (pruned or {}).get(key) or {}
        if seg.kind == "unroll":
            np_seg, st_seg = {}, {}
            for j, desc in enumerate(seg.descs):
                leaf = seg_pruned.get(f"layer{j}")
                pf = None if leaf is None else _union_layer(
                    leaf, 0, {k: leaf[k][0] for k in ("mask", "n_live")})
                x, npool, st = _apply_layer_paged(
                    sp[f"layer{j}"], desc, seg_pool[f"layer{j}"], x,
                    block_tables, pos, write_mask, cfg, pf, collect_stats,
                    backend, kv_dtype,
                )
                np_seg[f"layer{j}"] = npool
                if collect_stats:
                    st_seg[f"layer{j}"] = st
            new_pools[key] = np_seg
            stats_tree[key] = st_seg
        else:
            # the union buffers stay whole (the kernel picks the layer by
            # index, so no layer slice of them is copied); each layer's
            # masks and live counts ride the scan
            rows = {name: {k: leaf[k] for k in ("mask", "n_live")}
                    for name, leaf in seg_pruned.items()}

            def body(x_c, xs, _descs=seg.descs, _pruned=seg_pruned):
                lp_all, pool_all, (layer, rows_all) = xs
                np_out, st_out = {}, {}
                for j, desc in enumerate(_descs):
                    leaf = _pruned.get(f"pos{j}")
                    pf = None if leaf is None else _union_layer(
                        leaf, layer, rows_all[f"pos{j}"])
                    x_c, npool, st = _apply_layer_paged(
                        lp_all[f"pos{j}"], desc, pool_all[f"pos{j}"], x_c,
                        block_tables, pos, write_mask, cfg, pf, collect_stats,
                        backend, kv_dtype,
                    )
                    np_out[f"pos{j}"] = npool
                    st_out[f"pos{j}"] = st if collect_stats else jnp.zeros(())
                return x_c, (np_out, st_out)

            x, (np_seg, st_seg) = jax.lax.scan(
                body, x,
                (sp, seg_pool, (jnp.arange(seg.n, dtype=jnp.int32), rows)),
            )
            new_pools[key] = np_seg
            if collect_stats:
                stats_tree[key] = st_seg
    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params.get("head", {}), params["embed"], x, cfg)
    return logits, new_pools, (stats_tree if collect_stats else None)


def _union_layer(leaf: Dict, layer, rows: Dict) -> Dict:
    """One layer's view of a segment's union FF leaf: the whole stacked
    buffers, the layer's index in them, its masks and live count."""
    return {"wg": leaf["wg"], "w1": leaf["w1"], "w2": leaf["w2"],
            "layer": layer, "mask": rows["mask"], "n_live": rows["n_live"]}


def draft_loop_paged(
    params: Dict,
    cfg,
    pools: Dict,
    block_tables: jax.Array,  # [B, n_pages] int32, -1 = unallocated
    token: jax.Array,  # [B, 1] int32: last committed token per slot
    pos: jax.Array,  # [B] int32 committed KV length per slot
    k_r: jax.Array,  # [B] int32 per-slot draft lengths (<= num_steps)
    pruned: Optional[Dict] = None,  # union-compacted FF tree (the draft)
    *,
    num_steps: int,
    backend: str = "gather",
    kv_dtype: str = "fp32",
) -> Tuple[jax.Array, Dict]:
    """Fused k-token self-speculative draft loop: one device program.

    Runs ``num_steps`` greedy draft iterations of the ``[B, 1]`` paged
    decode step inside a single ``lax.scan`` — argmax feedback, draft-KV
    page writes, and the GRIFFIN union-compacted FF buffers all stay
    on device, so a round costs one dispatch and one host sync instead
    of ``num_steps`` of each (the serving-path host loop this replaces;
    ``PagedServer._run_speculative``).

    Per-slot masking: slot ``b`` participates in iteration ``i`` only
    while ``i < k_r[b]``.  A masked slot's KV write is suppressed
    exactly like the host loop's (``write_mask`` row False → trash-page
    redirect in the gather oracle, row skip in the fused kernel) and
    its carried token is frozen with ``jnp.where``, so its logits past
    ``k_r[b]`` are garbage that nothing consumes — the caller slices
    each slot's first ``k_r[b]`` drafts.

    ``num_steps`` is static (``max(k_r)`` at the call site), so the
    compiled-program count is bounded by ``spec_k`` distinct lengths.
    Greedy drafts are bit-identical to the per-token host loop: each
    iteration traces the very same ``decode_step_paged`` body, and
    ``jnp.argmax`` and ``np.argmax`` share first-max tie-breaking.

    Returns (draft tokens [B, num_steps] int32, new pools).
    """

    def body(carry, i):
        tok, pl = carry
        live = i < k_r  # [B] bool
        logits, pl, _ = decode_step_paged(
            params, cfg, pl, block_tables, tok, pos + i,
            write_mask=live[:, None], pruned=pruned, backend=backend,
            kv_dtype=kv_dtype,
        )
        nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        tok = jnp.where(live[:, None], nxt[:, None], tok)
        return (tok, pl), nxt

    (_, pools), drafts = jax.lax.scan(
        body, (token, pools), jnp.arange(num_steps, dtype=jnp.int32)
    )
    return jnp.swapaxes(drafts, 0, 1), pools


def verify_step_paged(
    params: Dict,
    cfg,
    pools: Dict,
    block_tables: jax.Array,  # [B, n_pages] int32, -1 = unallocated
    tokens: jax.Array,  # [B, k+1] int32: last committed token + k drafts
    pos: jax.Array,  # [B] int32 committed KV length per request
    write_mask: jax.Array,  # [B, k+1] bool
    backend: str = "gather",
    kv_dtype: str = "fp32",
) -> Tuple[jax.Array, Dict]:
    """Multi-token dense verify step for self-speculative decoding.

    Scores all ``k+1`` positions of a drafted continuation in one
    batched pass with the *full* (uncompacted) weights — the same
    ``paged_attn_step`` causal-masked path as a prefill chunk, but
    batched over decode slots with per-request positions.  Token
    ``tokens[b, i]`` sits at absolute position ``pos[b] + i``; its
    dense KV overwrites whatever the draft wrote there, so accepted
    positions end up with exactly the KV a vanilla dense decode would
    have written.  Rejected positions (``>= cache_len`` after the
    commit) hold stale KV that every reader masks out (page lifecycle
    contract in ``serving/paged.py``).

    Returns (logits [B, k+1, V], new pools).  Row ``i`` of the logits
    scores the position after input ``i`` — the acceptance walk over
    these rows lives in ``serving/sampling.py::greedy_verify`` /
    ``speculative_verify``.
    """
    logits, pools, _ = decode_step_paged(
        params, cfg, pools, block_tables, tokens, pos,
        write_mask=write_mask, pruned=None, collect_stats=False,
        backend=backend, kv_dtype=kv_dtype,
    )
    return logits, pools


def draft_verify_paged(
    params: Dict,
    cfg,
    pools: Dict,
    block_tables: jax.Array,  # [B, n_pages] int32, -1 = unallocated
    token: jax.Array,  # [B, 1] int32: last committed token per slot
    pos: jax.Array,  # [B] int32 committed KV length per slot
    k_r: jax.Array,  # [B] int32 per-slot draft lengths (0 = no drafting)
    row_live: jax.Array,  # [B] bool: slot holds a planned request
    pruned: Optional[Dict] = None,  # union-compacted FF tree (the draft)
    *,
    num_steps: int,
    spec_k: int,
    backend: str = "gather",
    kv_dtype: str = "fp32",
) -> Tuple[jax.Array, jax.Array, Dict]:
    """Whole speculative round — draft scan *and* dense verify — as one
    device program.

    ``draft_loop_paged`` already collapses the k draft steps into one
    dispatch, but a round then still pays a second dispatch + host sync
    to verify.  At decode batch sizes the per-dispatch overhead rivals
    the model compute, so fusing the verify in here halves the round's
    fixed cost: the drafts feed the ``[B, spec_k+1]`` verify matrix
    on-device (last committed token in column 0, each slot's drafts
    after it) and the host syncs once, pulling drafts and verify logits
    together after the single dispatch.

    ``row_live`` distinguishes an empty decode slot (verify row fully
    masked, like the vanilla step's dead rows) from a live request that
    drafted 0 tokens this round (pool pressure): the latter's verify
    row is just its last committed token, i.e. exactly a vanilla dense
    step for that slot.  ``num_steps`` may exceed ``spec_k`` (the
    caller pads it to a power of two to bound compiled-program count);
    surplus draft columns are dropped — every ``k_r`` is <= both.

    Returns (drafts [B, num_steps], verify logits [B, spec_k+1, V],
    new pools).
    """
    drafts, pools = draft_loop_paged(
        params, cfg, pools, block_tables, token, pos, k_r, pruned,
        num_steps=num_steps, backend=backend, kv_dtype=kv_dtype,
    )
    B = token.shape[0]
    cols = min(num_steps, spec_k)
    vtoks = jnp.concatenate(
        [token, drafts[:, :cols],
         jnp.zeros((B, spec_k - cols), jnp.int32)], axis=1)
    idx = jnp.arange(spec_k + 1, dtype=jnp.int32)[None, :]
    vmask = row_live[:, None] & (idx <= k_r[:, None])
    vlogits, pools = verify_step_paged(
        params, cfg, pools, block_tables, vtoks, pos, vmask,
        backend=backend, kv_dtype=kv_dtype,
    )
    return drafts, vlogits, pools


# ---------------------------------------------------------------------------
# GRIFFIN plumbing
# ---------------------------------------------------------------------------

def extract_ffn_tree(params: Dict, cfg) -> Dict:
    """Subtree of every GRIFFIN-prunable FF block (dense FF / MoE shared),
    mirroring the stats tree emitted by ``forward(collect_stats=True)``."""
    out: Dict[str, Any] = {}
    for i, seg in enumerate(build_plan(cfg)):
        key = f"seg{i}"
        seg_out = {}
        for j, desc in enumerate(seg.descs):
            name = f"pos{j}" if seg.kind == "scan" else f"layer{j}"
            if desc.ffn == "dense":
                seg_out[name] = params[key][name]["ffn"]
            elif desc.ffn == "moe" and cfg.num_shared_experts:
                seg_out[name] = params[key][name]["ffn"]["shared"]
        out[key] = seg_out
    return out


def pruned_ffn_specs(cfg, sparsity: Optional[float] = None, *,
                     gcfg=None, tier: Optional[float] = None,
                     profile=None) -> Dict:
    """ParamSpec tree of the GRIFFIN-compacted decode FF blocks (for the
    dry-run's abstract inputs), mirroring ``extract_ffn_tree``.

    Budgets come from the profile API (``griffin.plan_k_tree``): pass a
    ``gcfg`` (plus optional ``tier``/``profile``) for per-layer widths,
    or the legacy ``sparsity`` scalar, which maps to the uniform
    ``keep = 1 - sparsity`` budget.  Scan-stacked leaves take the widest
    instance's width (narrower instances ride with dead zero rows, see
    DESIGN.md section 16)."""
    from repro.core import griffin as griffin_lib

    if gcfg is None:
        if sparsity is None:
            raise ValueError("pruned_ffn_specs: pass sparsity or gcfg")
        gcfg = griffin_lib.GriffinConfig(sparsity=sparsity)
    ks = griffin_lib.plan_k_tree(cfg, gcfg, tier=tier, profile=profile)
    out: Dict[str, Any] = {}
    for i, seg in enumerate(build_plan(cfg)):
        key = f"seg{i}"
        seg_out = {}
        for j, desc in enumerate(seg.descs):
            name = f"pos{j}" if seg.kind == "scan" else f"layer{j}"
            path = f"{key}/{name}"
            if path not in ks:
                continue
            specs = ffn_lib.ffn_specs(cfg, d_ff=max(ks[path]))
            if seg.kind == "scan":
                specs = param_lib.stack_specs(specs, seg.n)
            seg_out[name] = specs
        out[key] = seg_out
    return out


def prune_stats_tree(stats: Dict, cfg) -> Dict:
    """Drop the zero-width placeholder leaves (layers without dense FF)."""
    out: Dict[str, Any] = {}
    for i, seg in enumerate(build_plan(cfg)):
        key = f"seg{i}"
        if key not in stats:
            continue
        seg_out = {}
        for j, desc in enumerate(seg.descs):
            name = f"pos{j}" if seg.kind == "scan" else f"layer{j}"
            has_ff = desc.ffn == "dense" or (
                desc.ffn == "moe" and cfg.num_shared_experts
            )
            if has_ff and name in stats[key]:
                seg_out[name] = stats[key][name]
        out[key] = seg_out
    return out
