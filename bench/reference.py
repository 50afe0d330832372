"""Plain reference of a served GRIFFIN request, and its gap reading.

A Llama-style decoder (RMSNorm, rotate-half RoPE, grouped-query
attention, SwiGLU) in straightforward ``jax.numpy``, float32 at
"highest" matmul precision, written from the published architecture and
the GRIFFIN paper, not from the program.  It imports nothing of the
program and reads only the weights the benchmark made.

GRIFFIN (arXiv 2404.01365, section 4.2) per FF layer: over the prompt's
tokens, ``s[j] = sum_t z[t, j]^2 / ||z[t]||^2`` with ``z = silu(x W_g) *
(x W_1)``; the ``k`` neurons of largest ``s`` are kept, and every
position after the prompt runs the FF on those ``k`` alone.  The prompt
itself runs dense.  Since attention is causal, one pass over the prompt
and the served tokens gives the statistic (from the prompt rows) and
every served position's logits at once.

The reading: at each served position, how far the served token's
logit lies below the reference's best (``max_v ref[v] - ref[served]``).
Greedy serving that computed the same function reads 0 up to rounding.
The control puts the same model in the program's place in a lower
precision (int8 weights, bf16 activations) and reads the same gap for
the token that it ranks first.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from bench.spec import ModelSpec

HI = jax.lax.Precision.HIGHEST
#: matmul weights the control quantizes (per output channel)
_MATMUL = ("wq", "wk", "wv", "wo", "wg", "w1", "w2", "head", "embed")


def _quantize(w: jax.Array, axis) -> jax.Array:
    """Symmetric per-channel int8 quantization; returns the dequantized
    bf16 weights (int8 values are exact in bf16)."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127)
    return (q * scale).astype(jnp.bfloat16)


def quantized(w: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Weights of the control: every matmul weight in int8 per output
    channel (the contraction axes share one scale), norms as they are."""
    # contraction axes, layer axis first where stacked
    axes = {"wq": 1, "wk": 1, "wv": 1, "wo": (1, 2), "wg": 1, "w1": 1,
            "w2": 1, "head": 0, "embed": 1}
    return {k: (_quantize(v, axes[k]) if k in _MATMUL else v)
            for k, v in w.items()}


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotate-half RoPE (Llama): x [T, n, hd], pos [T]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(s: ModelSpec, w: Dict[str, jax.Array], tokens: jax.Array,
           n_prompt: jax.Array, k: Optional[int], low: bool):
    """[T, V] float32 logits of the sequence ``tokens`` [T], and the
    [L, d_ff] mask of the FF neurons each layer keeps after the prompt.

    ``k``: experts each FF layer keeps after the prompt (None: dense).
    ``low``: the control's arithmetic, as the program serves bf16 (bf16
    matmul inputs, residual stream and cached keys and values, at
    default precision); otherwise float32 at "highest" precision."""
    T = tokens.shape[0]
    act = jnp.bfloat16 if low else jnp.float32
    prec = None if low else HI

    def rnd(a):
        # bf16 rounding that XLA keeps (a bf16 round trip through astype
        # may be dropped on the TPU as excess precision)
        return jax.lax.reduce_precision(a, exponent_bits=8,
                                        mantissa_bits=7) if low else a

    def mm(eq, a, b):
        return jnp.einsum(eq, a.astype(act), b.astype(act), precision=prec,
                          preferred_element_type=jnp.float32)

    pos = jnp.arange(T, dtype=jnp.int32)
    x = w["embed"][tokens].astype(jnp.float32)
    G = s.heads // s.kv_heads
    causal = pos[None, :] <= pos[:, None]
    gen = (pos >= n_prompt)[:, None]

    def layer(x, lw):
        h = _rms(x, lw["attn_norm"], s.norm_eps)
        q = _rope(mm("td,dnk->tnk", h, lw["wq"]), pos, s.rope_theta)
        kk = rnd(_rope(mm("td,dnk->tnk", h, lw["wk"]), pos, s.rope_theta))
        v = rnd(mm("td,dnk->tnk", h, lw["wv"]))
        q = q.reshape(T, s.kv_heads, G, s.head_dim)
        sc = mm("tngk,snk->ngts", q, kk) / math.sqrt(s.head_dim)
        sc = jnp.where(causal, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        ctx = mm("ngts,snk->tngk", p, v).reshape(T, s.heads, s.head_dim)
        x = rnd(x + mm("tnk,nkd->td", ctx, lw["wo"]))
        h = _rms(x, lw["ffn_norm"], s.norm_eps)
        z = jax.nn.silu(mm("td,df->tf", h, lw["wg"])) \
            * mm("td,df->tf", h, lw["w1"])
        keep = jnp.ones((s.d_ff,), bool)
        if k is not None:
            prompt_rows = (pos < n_prompt)[:, None]
            zp = jnp.where(prompt_rows, z, 0.0)
            row = jnp.sum(zp * zp, -1, keepdims=True)
            stat = jnp.sum(zp * zp / jnp.where(row > 0, row, 1.0), 0)
            keep = jnp.zeros((s.d_ff,), bool).at[
                jax.lax.top_k(stat, k)[1]].set(True)
            z = jnp.where(gen & ~keep[None], 0.0, z)
        x = rnd(x + mm("tf,fd->td", z, lw["w2"]))
        return x, keep

    stacked = {n: w[n] for n in ("attn_norm", "ffn_norm", "wq", "wk", "wv",
                                 "wo", "wg", "w1", "w2")}
    x, keeps = jax.lax.scan(layer, x, stacked)
    h = _rms(x, w["final_norm"], s.norm_eps)
    return mm("td,dv->tv", h, w["head"]), keeps


@partial(jax.jit, static_argnames=("s", "k", "control"))
def gaps(s: ModelSpec, w: Dict[str, jax.Array], tokens: jax.Array,
         n_prompt: jax.Array, n_total: jax.Array, k: Optional[int],
         control: bool = False):
    """Per-position gaps over one request.

    ``tokens`` [T]: prompt then served tokens, zero-padded past
    ``n_total``.  Position ``t`` in ``[n_prompt - 1, n_total - 2]``
    predicts served token ``tokens[t + 1]``.  Returns ``(served, ctl)``
    [T] float32: ``max ref[t] - ref[t, tokens[t + 1]]``, and (with
    ``control``) the same for the control's first-ranked token; 0 off
    the served positions."""
    ref, _ = logits(s, w, tokens, n_prompt, k, low=False)
    T = tokens.shape[0]
    t = jnp.arange(T)
    on = (t >= n_prompt - 1) & (t <= n_total - 2)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    best = jnp.max(ref, -1)
    served = best - jnp.take_along_axis(ref, nxt[:, None], 1)[:, 0]
    served = jnp.where(on, served, 0.0)
    if not control:
        return served, jnp.zeros_like(served)
    low, _ = logits(s, quantized(w), tokens, n_prompt, k, low=True)
    pick = jnp.argmax(low, -1)
    ctl = best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
    return served, jnp.where(on, ctl, 0.0)


@partial(jax.jit, static_argnames=("s", "k"))
def kept(s: ModelSpec, w: Dict[str, jax.Array], tokens: jax.Array,
         n_prompt: jax.Array, k: int) -> jax.Array:
    """[L, d_ff] bool: the neurons each FF layer keeps for the prompt
    ``tokens[:n_prompt]`` (zero-padded past it)."""
    return logits(s, w, tokens, n_prompt, k, low=False)[1]
