"""Mixture-of-Experts FF block: top-k routing, sort-based capacity dispatch,
batched expert GEMMs, optional shared experts.

Dispatch is **sort-based** (argsort by expert id + searchsorted group
starts), which avoids the O(T*E*C) one-hot dispatch einsums of
GShard-style implementations — the dominant-term killer at 32k prefill.
Tokens beyond an expert's static capacity are dropped (standard
capacity-factor semantics); the residual path carries them.

Expert tensors carry the "experts" logical axis; under the production
rules that maps to the mesh ``model`` axis (or ``(data, model)`` for
deepseek's 256 experts == the full 16x16 pod), giving expert parallelism
with GSPMD-inserted all-to-alls around the dispatch/combine gathers.
Long token streams are processed in static chunks (scan) to bound the
dispatch buffers.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.common import activation_fn
from repro.models.layers import ffn as ffn_lib
from repro.models.param import ParamSpec


def moe_specs(cfg) -> Dict[str, ParamSpec]:
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    specs = {
        "router": ParamSpec((D, E), ("embed", None), dtype="float32"),
        "w1": ParamSpec((E, D, F), ("experts", "embed", "mlp")),
        "w2": ParamSpec((E, F, D), ("experts", "mlp", "embed")),
    }
    if cfg.glu:
        specs["wg"] = ParamSpec((E, D, F), ("experts", "embed", "mlp"))
    if cfg.num_shared_experts:
        Fs = cfg.moe_d_ff * cfg.num_shared_experts
        specs["shared"] = ffn_lib.ffn_specs(cfg, d_ff=Fs)
    return specs


def _capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.experts_per_token * cfg.capacity_factor / cfg.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8, floor at 8


def _route(params, x, cfg):
    """x [T,D] -> (gate [T,k] fp32, idx [T,k] int32, aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    # switch-style load-balance aux loss
    E = cfg.num_experts
    me = jnp.mean(probs, axis=0)  # [E] mean router prob
    one_hot = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
    fe = jnp.mean(one_hot, axis=0)  # fraction routed (top-1 proxy)
    aux = E * jnp.sum(me * fe)
    return gate, idx, aux


def _dispatch_combine(params, x, gate, idx, cfg):
    """Sort-based capacity-buffered expert compute for a token chunk.

    x [T,D], gate/idx [T,k]  ->  y [T,D]
    """
    T, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = _capacity(T, cfg)

    flat_e = idx.reshape(-1)  # [T*k]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    keep = pos_in_e < C
    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)  # overflow -> slot E*C
    src_token = order // k

    # dispatch: buf[e, c] = x[token routed to (e, c)]
    buf = jnp.zeros((E * C + 1, D), x.dtype)
    buf = buf.at[dest].set(x[src_token], mode="drop")
    buf = buf[: E * C].reshape(E, C, D)
    buf = constrain(buf, ("experts", "cap", "act_embed"))

    # batched expert GEMMs
    act = activation_fn(cfg.activation)
    h1 = jnp.einsum("ecd,edf->ecf", buf, params["w1"])
    if "wg" in params:
        hg = jnp.einsum("ecd,edf->ecf", buf, params["wg"])
        z = act(hg) * h1
    else:
        z = act(h1)
    yexp = jnp.einsum("ecf,efd->ecd", z, params["w2"])
    yexp = constrain(yexp, ("experts", "cap", "act_embed"))

    # combine: gather back, weight by gate, scatter-add per token
    ypad = jnp.concatenate([yexp.reshape(E * C, D), jnp.zeros((1, D), x.dtype)], 0)
    contrib = ypad[dest] * gate.reshape(-1)[order][:, None].astype(x.dtype)
    y = jnp.zeros((T, D), x.dtype).at[src_token].add(contrib)
    return y


def _ep_shard_info(cfg):
    """If an (axis_rules) mesh with a usable ``model`` axis is active,
    return (mesh, n_model) for the shard_map EP path, else None.

    The explicit path exists because GSPMD lowers the sort-based
    dispatch's cross-shard gathers to replicate+all-reduce of the FULL
    activation (measured: 3.8 GB fp32 AR per layer per microbatch on
    deepseek train).  With shard_map, tokens stay data-sharded and
    replicated over ``model``; each model shard computes only its local
    experts and the combine is a single psum of the [T_local, D] output
    — wire bytes per chip drop to ~2x output size.
    """
    from repro.distributed.sharding import active_rules

    mesh, rules = active_rules()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    # 2D-sharded experts (deepseek) would be re-gathered over data by a
    # model-only shard_map in_spec — keep those on the GSPMD path.
    exp_rule = (rules or {}).get("experts")
    if isinstance(exp_rule, tuple) and len(exp_rule) > 1:
        return None
    n_model = dict(mesh.shape)["model"]
    if n_model <= 1 or cfg.num_experts % n_model != 0:
        return None
    return mesh, n_model


def _dispatch_combine_local(params_loc, x, gate, idx, cfg, e0: int, e_loc: int,
                            cap_experts: int = 0):
    """Capacity-buffered compute of the LOCAL expert slice [e0, e0+e_loc).

    Same sort-based scheme as ``_dispatch_combine`` but assignments to
    remote experts are dropped locally (they're computed by their own
    shard); all gathers/scatters index only local data.
    ``cap_experts``: expert-pool size for the capacity formula (the
    routing pool may be smaller than num_experts under group limits).
    """
    T, D = x.shape
    k = cfg.experts_per_token
    pool = cap_experts or cfg.num_experts
    C = max(8, -(-int(T * k * cfg.capacity_factor / pool) // 8) * 8)

    flat_e = idx.reshape(-1)
    local = (flat_e >= e0) & (flat_e < e0 + e_loc)
    flat_le = jnp.where(local, flat_e - e0, e_loc)  # remote -> overflow id
    order = jnp.argsort(flat_le, stable=True)
    sorted_e = flat_le[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e_loc), side="left")
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - starts[
        jnp.clip(sorted_e, 0, e_loc - 1)
    ].astype(jnp.int32)
    keep = (sorted_e < e_loc) & (pos_in_e < C)
    dest = jnp.where(keep, sorted_e * C + pos_in_e, e_loc * C)
    src_token = order // k

    buf = jnp.zeros((e_loc * C + 1, D), x.dtype)
    buf = buf.at[dest].set(x[src_token], mode="drop")
    buf = buf[: e_loc * C].reshape(e_loc, C, D)

    act = activation_fn(cfg.activation)
    h1 = jnp.einsum("ecd,edf->ecf", buf, params_loc["w1"])
    if "wg" in params_loc:
        z = act(jnp.einsum("ecd,edf->ecf", buf, params_loc["wg"])) * h1
    else:
        z = act(h1)
    yexp = jnp.einsum("ecf,efd->ecd", z, params_loc["w2"])

    ypad = jnp.concatenate([yexp.reshape(e_loc * C, D),
                            jnp.zeros((1, D), x.dtype)], 0)
    contrib = ypad[dest] * gate.reshape(-1)[order][:, None].astype(x.dtype)
    return jnp.zeros((T, D), x.dtype).at[src_token].add(contrib)


def _moe_routed_ep(params, xt, gate, idx, cfg, mesh, n_model):
    """shard_map EP: experts sharded over ``model``; tokens data-sharded
    and replicated over ``model``; combine = psum over ``model``."""
    from jax.sharding import PartitionSpec as P

    e_loc = cfg.num_experts // n_model
    tok_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tok_spec = P(tok_axes if tok_axes else None, None)
    w_spec = {k: P("model") for k in ("w1", "w2") if k in params}
    if "wg" in params:
        w_spec["wg"] = P("model")
    expert_params = {k: params[k] for k in w_spec}

    def inner(wp, x_l, g_l, i_l):
        midx = jax.lax.axis_index("model")
        y = _dispatch_combine_local(
            wp, x_l, g_l, i_l, cfg, e0=midx * e_loc, e_loc=e_loc
        )
        return jax.lax.psum(y, "model")

    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(w_spec, tok_spec, tok_spec, tok_spec),
        out_specs=tok_spec,
        check_vma=False,
    )(expert_params, xt, gate, idx)


def _ep2d_info(cfg):
    """Group-limited 2D EP: experts sharded (data, model); usable when
    ``cfg.moe_group_limit > 0`` and the division works out."""
    from repro.distributed.sharding import active_rules

    mesh, rules = active_rules()
    if mesh is None or cfg.moe_group_limit <= 0:
        return None
    if "model" not in mesh.axis_names or "data" not in mesh.axis_names:
        return None
    exp_rule = (rules or {}).get("experts")
    if not (isinstance(exp_rule, tuple) and set(exp_rule) == {"data", "model"}):
        return None
    nd = dict(mesh.shape)["data"]
    nm = dict(mesh.shape)["model"]
    if cfg.num_experts % (nd * nm) != 0:
        return None
    return mesh, nd, nm


def _moe_grouped_ep2d(params, xt, cfg, mesh, nd, nm):
    """Group-limited routing over 2D-sharded experts.

    Tokens route ONLY to the E/nd experts of their own data row (the
    deepseek node-limited-routing idea at row granularity) — so no token
    ever crosses the ``data`` axis, and the only collective is the
    per-row combine psum over ``model``.  Returns (y, aux).
    """
    from jax.sharding import PartitionSpec as P

    E = cfg.num_experts
    E_row = E // nd
    E_sub = E_row // nm
    k = cfg.experts_per_token
    tok_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tok_spec = P(tok_axes if tok_axes else None, None)
    w_spec = {kk: P(("data", "model")) for kk in ("w1", "w2", "wg")
              if kk in params}
    expert_params = {kk: params[kk] for kk in w_spec}
    router_spec = P()

    def inner(router_w, wp, x_l):
        row = jax.lax.axis_index("data")
        col = jax.lax.axis_index("model")
        # route within the row's expert group only
        logits = jnp.einsum("td,de->te", x_l.astype(jnp.float32), router_w)
        row_ids = row * E_row + jnp.arange(E_row)
        logits_row = jnp.take(logits, row_ids, axis=1)  # [T, E_row]
        probs = jax.nn.softmax(logits_row, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)  # idx in [0, E_row)
        gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
        me = jnp.mean(jax.nn.softmax(logits, -1), axis=0)
        fe = jnp.mean(jax.nn.one_hot(row_ids[idx[:, 0]], E, dtype=jnp.float32), 0)
        aux = E * jnp.sum(me * fe)
        y = _dispatch_combine_local(
            wp, x_l, gate, idx, cfg, e0=col * E_sub, e_loc=E_sub,
            cap_experts=E_row,
        )
        y = jax.lax.psum(y, "model")
        return y, jax.lax.pmean(aux, "model")

    y, aux = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(router_spec, w_spec, tok_spec),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )(params["router"], expert_params, xt)
    return y, jnp.mean(aux)


def moe_forward(
    params: Dict,
    x: jax.Array,
    cfg,
    chunk_tokens: int = 16_384,
    collect_stats: bool = False,
    want_z: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    """x: [B,S,D] -> (y [B,S,D], aux_loss, shared-expert stats or None).

    GRIFFIN statistic is collected on the **shared expert** (the always-on
    dense FF) — routed experts are already adaptively sparse (DESIGN.md #4).
    """
    B, S, D = x.shape
    xt = x.reshape(B * S, D)

    ep2d = _ep2d_info(cfg)
    if ep2d is not None:
        mesh, nd, nm = ep2d
        y, aux = _moe_grouped_ep2d(params, xt, cfg, mesh, nd, nm)
        y = y.reshape(B, S, D)
        stats = None
        if "shared" in params:
            ys, stats = ffn_lib.ffn_forward(
                params["shared"], x, cfg, collect_stats=collect_stats,
                want_z=want_z,
            )
            y = y + ys
        return y, aux, stats

    gate, idx, aux = _route(params, xt, cfg)

    T = B * S
    ep = _ep_shard_info(cfg)
    chunk = min(chunk_tokens, T)
    if T % chunk != 0:
        chunk = T  # smoke shapes: do it in one piece
    n = T // chunk
    if ep is not None:
        mesh, n_model = ep
        if n > 1:
            def body(_, args):
                xc, gc, ic = args
                return None, _moe_routed_ep(params, xc, gc, ic, cfg, mesh, n_model)
            _, ys = jax.lax.scan(
                body, None,
                (xt.reshape(n, chunk, D), gate.reshape(n, chunk, -1),
                 idx.reshape(n, chunk, -1)),
            )
            y = ys.reshape(T, D)
        else:
            y = _moe_routed_ep(params, xt, gate, idx, cfg, mesh, n_model)
    elif n > 1:
        def body(_, args):
            xc, gc, ic = args
            return None, _dispatch_combine(params, xc, gc, ic, cfg)
        _, ys = jax.lax.scan(
            body,
            None,
            (
                xt.reshape(n, chunk, D),
                gate.reshape(n, chunk, -1),
                idx.reshape(n, chunk, -1),
            ),
        )
        y = ys.reshape(T, D)
    else:
        y = _dispatch_combine(params, xt, gate, idx, cfg)
    y = y.reshape(B, S, D)

    stats = None
    if "shared" in params:
        ys, stats = ffn_lib.ffn_forward(
            params["shared"], x, cfg, collect_stats=collect_stats, want_z=want_z
        )
        y = y + ys
    return y, aux, stats


def moe_decode(
    params: Dict,
    pruned_shared: Optional[Dict],
    x: jax.Array,
    cfg,
) -> jax.Array:
    """Decode-phase MoE: routed experts as usual; shared expert optionally
    replaced by its GRIFFIN-compacted version."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    gate, idx, _ = _route(params, xt, cfg)
    y = _dispatch_combine(params, xt, gate, idx, cfg).reshape(B, S, D)
    if pruned_shared is not None:
        ys, _ = ffn_lib.ffn_forward(pruned_shared, x, cfg)
        y = y + ys
    elif "shared" in params:
        ys, _ = ffn_lib.ffn_forward(params["shared"], x, cfg)
        y = y + ys
    return y
