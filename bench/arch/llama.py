"""Yardstick of a Llama-form decoder: RMSNorm, rotate-half RoPE,
grouped-query attention and an untied SwiGLU feed-forward, every layer
alike.  A configuration file names it with ``"yardstick": "llama"``.

It holds everything the benchmark computes from this block's shapes
(the interface is in ``bench/spec.py``, :func:`~bench.spec.yardstick`):

- :class:`ModelSpec`: the sizes, from the configuration file;
- :func:`program_config`: the program's ``ModelConfig`` for the file;
- :func:`make`: the weights, on the device from the seed in one jitted
  call, and :func:`program_params`, the program's tree over them;
- :func:`gaps` (and :func:`kept`): the plain reference and its int8
  control;
- :func:`keep`, :func:`token_flops` and ``KERNEL_COUNTS``: GRIFFIN's
  kept neurons at a tier, and the operations and bytes of the work.

Weights.  The benchmark makes the weights, not the program: the
reference reads the plain stacked dict that :func:`make` returns, and
the program gets the same arrays rearranged into its own parameter tree
(:func:`program_params`), so the two start from identical bits and the
reference takes nothing the program has made.

Reference.  A Llama-style decoder in straightforward ``jax.numpy``,
float32 at "highest" matmul precision, written from the published
architecture and the GRIFFIN paper, not from the program.  It imports
nothing of the program and reads only the weights the benchmark made.

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

Counts.  Operations and bytes that the served work needs, from shapes.
These are the least the algorithm must do, not what an implementation
happens to do: attention reads each attended key and value once and
writes each new one once; a token's matmuls touch each weight once.  A
roofline share computed from them therefore cannot pass 100% unless
the device time leaves work out.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

# -- sizes and the program's configuration ------------------------------------

#: published config key -> the program's ModelConfig field
_PROGRAM_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}
_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


@dataclass(frozen=True)
class ModelSpec:
    """The sizes the yardstick (weights, reference, FLOP counts) reads."""
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    dtype: str

    @classmethod
    def from_file(cls, c: Dict[str, Any]) -> "ModelSpec":
        if c.get("hidden_act") != "silu":
            raise ValueError(f"{c['name']}: only SwiGLU (silu) models")
        if c["tie_word_embeddings"]:
            raise ValueError(f"{c['name']}: tied embeddings are not "
                             f"supported yet (no cell needs them)")
        return cls(
            name=c["name"], layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            dtype=_DTYPES[c["torch_dtype"]],
        )


def program_config(c: Dict[str, Any]):
    """The program's ModelConfig for a configuration file: the registry
    entry named by ``arch`` with every size replaced by the file's."""
    from repro.configs.registry import get_config

    base = get_config(c["arch"])
    kw = {field: c[key] for key, field in _PROGRAM_FIELDS.items()}
    kw["dtype"] = _DTYPES[c["torch_dtype"]]
    kw["name"] = c["name"]
    kw["griffin"] = bool(c["serving"]["griffin"])
    return base.replace(**kw)


# -- weights ------------------------------------------------------------------

def shapes(s: ModelSpec) -> Dict[str, tuple]:
    L, D, H, KV, hd, F, V = (s.layers, s.d_model, s.heads, s.kv_heads,
                             s.head_dim, s.d_ff, s.vocab)
    out = {
        "embed": (V, D), "attn_norm": (L, D), "ffn_norm": (L, D),
        "final_norm": (D,),
        "wq": (L, D, H, hd), "wk": (L, D, KV, hd), "wv": (L, D, KV, hd),
        "wo": (L, H, hd, D), "wg": (L, D, F), "w1": (L, D, F),
        "w2": (L, F, D), "head": (D, V),
    }
    return out


def _std(s: ModelSpec, name: str) -> float:
    if name == "embed":
        return 1.0
    fan_in = {"wo": s.heads * s.head_dim, "w2": s.d_ff}.get(name, s.d_model)
    return fan_in ** -0.5


#: lognormal sigma of the per-neuron scales of ``w1`` (see :func:`make`)
NEURON_SIGMA = 1.0


@partial(jax.jit, static_argnums=(0,))
def make(s: ModelSpec, key: jax.Array) -> Dict[str, jax.Array]:
    """Fan-in scaled normal weights, RMSNorm scales ``1 + 0.1 N(0, 1)``,
    all in the served dtype.

    Each FF neuron's ``w1`` column is scaled by ``exp(NEURON_SIGMA *
    N(0, 1))`` (normalized to unit RMS per layer), so neurons differ in
    size as in trained models.  With i.i.d. weights every neuron is
    alike, GRIFFIN's top-k boundary is dense, and rounding alone swaps
    tens of boundary neurons per layer between any two computations of
    the statistic (bf16 program and float32 reference included)."""
    dt = jnp.dtype(s.dtype)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(s).items())):
        k = jax.random.fold_in(key, i)
        g = jax.random.normal(k, shape, jnp.float32)
        if name.endswith("norm"):
            out[name] = (1.0 + 0.1 * g).astype(dt)
            continue
        g = g * _std(s, name)
        if name == "w1":
            c = jnp.exp(NEURON_SIGMA * jax.random.normal(
                jax.random.fold_in(key, 1000), (s.layers, 1, s.d_ff)))
            g = g * c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True))
        out[name] = g.astype(dt)
    return out


def program_params(w: Dict[str, jax.Array], cfg) -> Dict:
    """The program's parameter tree over the same arrays (no copy):
    every layer in one scan-stacked segment, as ``decoder.build_plan``
    lays out a uniform attention + dense-FF stack."""
    from repro.models import decoder

    plan = decoder.build_plan(cfg)
    if len(plan) != 1 or plan[0].kind != "scan" or len(plan[0].descs) != 1:
        raise ValueError(f"{cfg.name}: expected one uniform scanned stack")
    tree = {
        "embed": {"table": w["embed"]},
        "seg0": {"pos0": {
            "mixer_norm": {"scale": w["attn_norm"]},
            "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "ffn_norm": {"scale": w["ffn_norm"]},
            "ffn": {k: w[k] for k in ("w1", "w2", "wg")},
        }},
        "final_norm": {"scale": w["final_norm"]},
        "head": {"w": w["head"]},
    }
    want = decoder.abstract_params(cfg)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{cfg.name}: weights do not match the program's "
                         f"parameter tree")
    return tree


# -- the reference and its control --------------------------------------------

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


# -- counts -------------------------------------------------------------------

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


# -- the interface's remaining entries --------------------------------------

def keep(s: ModelSpec, tier: float) -> int:
    """GRIFFIN's kept neurons at a tier: ``round(d_ff * tier)``."""
    return max(1, min(s.d_ff, int(round(s.d_ff * tier))))


#: kernel -> (flops, bytes) of one call for one request row and one
#: layer; a step calls each kernel once per layer
KERNEL_COUNTS = {"paged_attn": attn_call}
