"""Feed-forward blocks (GLU and non-GLU) with GRIFFIN instrumentation.

The FF block is the paper's object of study:

    FF(x)  = FF2(FF1(x)),      z = FF1(x)            (eq. 1)
    FF1(x) = sigma(W_g x) * (W_1 x)                   (GLU, eq. 3)
    FF1(x) = sigma(W_1 x)                             (non-GLU, eq. 2)

``ffn_forward(..., collect_stats=True)`` additionally returns the
per-sample squared GRIFFIN statistic

    s_sq[b, j] = sum_t  z[b,t,j]^2 / ||z[b,t,:]||^2   (eq. 6, squared)

computed in a streaming, fp32-accurate way (never materializes Z-bar).
``compact_ffn_params`` performs the paper's reparameterization: select
rows of W_g/W_1 (and biases) and columns of W_2 for an expert set E.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain, psum_if_tp
from repro.kernels.griffin_ffn import griffin_ffn
from repro.models.common import activation_fn
from repro.models.param import ParamSpec


def ffn_specs(cfg, d_ff: Optional[int] = None, glu: Optional[bool] = None) -> Dict[str, ParamSpec]:
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    g = cfg.glu if glu is None else glu
    specs = {
        "w1": ParamSpec((D, F), ("embed", "mlp")),
        "w2": ParamSpec((F, D), ("mlp", "embed")),
    }
    if g:
        specs["wg"] = ParamSpec((D, F), ("embed", "mlp"))
    if cfg.use_bias:
        specs["b1"] = ParamSpec((F,), ("mlp",), init="zeros")
        specs["b2"] = ParamSpec((D,), ("act_embed",), init="zeros")
        if g:
            specs["bg"] = ParamSpec((F,), ("mlp",), init="zeros")
    return specs


def ffn_activations(params: Dict, x: jax.Array, cfg) -> jax.Array:
    """z = FF1(x).  x: [..., D] -> z: [..., F]."""
    act = activation_fn(cfg.activation)
    h1 = jnp.einsum("...d,df->...f", x, params["w1"])
    if "b1" in params:
        h1 = h1 + params["b1"]
    if "wg" in params:
        hg = jnp.einsum("...d,df->...f", x, params["wg"])
        if "bg" in params:
            hg = hg + params["bg"]
        z = act(hg) * h1
    else:
        z = act(h1)
    return z


def griffin_stat_sq(z: jax.Array) -> jax.Array:
    """Per-sample squared statistic s^2 from activations z [B,S,F] (eq. 6).

    s_sq[b, j] = sum_t z[b,t,j]^2 / ||z[b,t]||^2  — token rows normalized
    to unit L2 before column-norms, all in fp32.

    Under shard_map tensor parallelism ``z`` is shard-local along F, so
    the per-token row norm is a partial sum — all-reduced across shards
    (``psum_if_tp``) so every local column is normalized by the *global*
    row norm; the statistic itself stays shard-local (the TP step
    all-gathers it for host-side selection, see ``distributed.tp``).
    """
    zf = z.astype(jnp.float32)
    row = jnp.sum(jnp.square(zf), axis=-1, keepdims=True)  # [B,S,1]
    row = psum_if_tp(row)
    inv = jnp.where(row > 0, 1.0 / row, 0.0)
    return jnp.sum(jnp.square(zf) * inv, axis=-2)  # [B,F]


def ffn_forward(
    params: Dict,
    x: jax.Array,
    cfg,
    collect_stats: bool = False,
    want_z: bool = False,
) -> Tuple[jax.Array, Optional[Dict]]:
    """x: [B,S,D] -> (y [B,S,D], stats or None).

    stats = {s_sq [B,F] (GRIFFIN eq. 6), x_sq [D], z_sq [F] (Adaptive
    Wanda norms), (z [B,S,F] if want_z — flocking heat maps)}.
    """
    z = ffn_activations(params, x, cfg)
    z = constrain(z, ("batch", "seq", "mlp"))
    stats = None
    if collect_stats:
        xf = x.astype(jnp.float32)
        zf = z.astype(jnp.float32)
        stats = {
            "s_sq": griffin_stat_sq(z),
            "x_sq": jnp.sum(jnp.square(xf), axis=(0, 1)),
            "z_sq": jnp.sum(jnp.square(zf), axis=(0, 1)),
        }
        if want_z:
            stats["z"] = z
    # sharded F axis -> the down-projection is a partial sum per shard
    y = psum_if_tp(jnp.einsum("...f,fd->...d", z, params["w2"]))
    if "b2" in params:
        y = y + params["b2"]
    return y, stats


def ffn_forward_union(pruned: Dict, x: jax.Array, cfg) -> jax.Array:
    """FF forward over the decode tick's union-compacted buffer.

    The paged serving path keeps one buffer per FF layer holding the
    union of the live requests' selected neurons (``serving/server.py``);
    ``pruned`` is one layer's view of it: ``wg``/``w1``/``w2`` the
    segment's stacked neuron-row buffers [L, C, D] and ``layer`` this
    layer's index in them, ``mask`` [B, C] each batch row's own neurons,
    ``n_live`` [1] the blocks in use.  The kernel reads only those
    blocks (``kernels/griffin_ffn.py``).  x: [B, S, D]; every token of
    a row takes the row's mask.
    """
    B, S, D = x.shape
    mask = pruned["mask"] if S == 1 else jnp.repeat(pruned["mask"], S, axis=0)
    y = griffin_ffn(
        x.reshape(B * S, D), pruned["wg"], pruned["w1"], pruned["w2"], mask,
        pruned["layer"], pruned["n_live"][0], activation=cfg.activation,
    )
    # the buffer shards along its neuron axis (each shard holds the union
    # of its own F-range): the down-projection is a partial sum per shard
    return psum_if_tp(y).astype(x.dtype).reshape(B, S, D)


def compact_ffn_params(params: Dict, idx: jax.Array, shards: int = 1) -> Dict:
    """GRIFFIN reparameterization (section 4.2): gather expert neurons E.

    idx: [k] int32 neuron indices (sorted). Returns a k-wide FF block.

    ``shards > 1`` (with per-shard balanced selection): the gather is
    reformulated as a *shard-local* ``take_along_axis`` over the TP axis
    — idx is guaranteed to contain exactly k/shards indices inside each
    contiguous F/shards range, so no cross-shard weight movement exists
    and GSPMD lowers it collective-free (a plain ``take`` along the
    sharded axis costs a full replicate+all-reduce — measured 10 GB/chip
    on command-r prefill).
    """
    F = params["w1"].shape[1]
    k = idx.shape[0]

    if shards > 1 and F % shards == 0 and k % shards == 0:
        fs, ks = F // shards, k // shards
        local = (idx.reshape(shards, ks)
                 - (jnp.arange(shards, dtype=idx.dtype) * fs)[:, None])

        def take_cols(w):  # [D, F] -> [D, k]
            D = w.shape[0]
            wr = w.reshape(D, shards, fs)
            out = jnp.take_along_axis(wr, local[None], axis=2)
            return out.reshape(D, k)

        def take_rows(w):  # [F, D] -> [k, D]
            D = w.shape[1]
            wr = w.reshape(shards, fs, D)
            out = jnp.take_along_axis(wr, local[:, :, None], axis=1)
            return out.reshape(k, D)

        def take_vec(b):  # [F] -> [k]
            return jnp.take_along_axis(b.reshape(shards, fs), local, axis=1
                                       ).reshape(k)

        out = {"w1": take_cols(params["w1"]), "w2": take_rows(params["w2"])}
        if "wg" in params:
            out["wg"] = take_cols(params["wg"])
        if "b1" in params:
            out["b1"] = take_vec(params["b1"])
        if "bg" in params:
            out["bg"] = take_vec(params["bg"])
        if "b2" in params:
            out["b2"] = params["b2"]
        return out

    out = {
        "w1": jnp.take(params["w1"], idx, axis=1),
        "w2": jnp.take(params["w2"], idx, axis=0),
    }
    if "wg" in params:
        out["wg"] = jnp.take(params["wg"], idx, axis=1)
    if "b1" in params:
        out["b1"] = jnp.take(params["b1"], idx, axis=0)
    if "bg" in params:
        out["bg"] = jnp.take(params["bg"], idx, axis=0)
    if "b2" in params:
        out["b2"] = params["b2"]
    return out


def pruned_specs(cfg, k: int, d_ff: Optional[int] = None) -> Dict[str, ParamSpec]:
    """Specs of the compacted decode-phase FF block (for dry-run inputs)."""
    return ffn_specs(cfg, d_ff=k)
