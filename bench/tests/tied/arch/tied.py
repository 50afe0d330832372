"""Yardstick of a toy second architecture, for the tests alone: a
decoder whose LM head is its embedding table (tied), with the input
embedding scaled by sqrt(width) as the program serves tied tables.

Its sizes, weight names and program tree differ from the Llama
yardstick's, so that a test can show that a new architecture enters the
benchmark as new files.  It serves tier 1.0 only (no GRIFFIN
reference), and counts no kernel: ``KERNEL_COUNTS`` is empty.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class ModelSpec:
    name: str
    layers: int
    width: int
    heads: int
    groups: int  # key/value heads
    hsize: int  # per head
    ff: int
    vocab: int
    theta: float
    eps: float

    @classmethod
    def from_file(cls, c: Dict[str, Any]) -> "ModelSpec":
        if not c["tie_word_embeddings"] or c.get("hidden_act") != "silu":
            raise ValueError(f"{c['name']}: only tied SwiGLU models")
        return cls(
            name=c["name"], layers=c["num_hidden_layers"],
            width=c["hidden_size"], heads=c["num_attention_heads"],
            groups=c["num_key_value_heads"], hsize=c["head_dim"],
            ff=c["intermediate_size"], vocab=c["vocab_size"],
            theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]))


def program_config(c: Dict[str, Any]):
    from repro.configs.registry import get_config

    s = ModelSpec.from_file(c)
    return get_config(c["arch"]).replace(
        name=s.name, num_layers=s.layers, d_model=s.width,
        num_heads=s.heads, num_kv_heads=s.groups, head_dim=s.hsize,
        d_ff=s.ff, vocab_size=s.vocab, rope_theta=s.theta,
        tie_embeddings=True, dtype=c["torch_dtype"],
        griffin=bool(c["serving"]["griffin"]))


def _shapes(s: ModelSpec) -> Dict[str, tuple]:
    L, D, F = s.layers, s.width, s.ff
    return {
        "tok": (s.vocab, D), "ln_attn": (L, D), "ln_ff": (L, D),
        "ln_out": (D,), "q": (L, D, s.heads, s.hsize),
        "k": (L, D, s.groups, s.hsize), "v": (L, D, s.groups, s.hsize),
        "o": (L, s.heads, s.hsize, D), "gate": (L, D, F), "up": (L, D, F),
        "down": (L, F, D),
    }


@partial(jax.jit, static_argnums=(0,))
def make(s: ModelSpec, key: jax.Array) -> Dict[str, jax.Array]:
    """Fan-in scaled normals (the table at 1/sqrt(width), so that the
    scaled embedding is a unit normal), norm scales ``1 + 0.1 N(0, 1)``."""
    fan_in = {"o": s.heads * s.hsize, "down": s.ff}
    out = {}
    for i, (name, shape) in enumerate(sorted(_shapes(s).items())):
        g = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.startswith("ln"):
            out[name] = 1.0 + 0.1 * g
        else:
            out[name] = g * fan_in.get(name, s.width) ** -0.5
    return out


def program_params(w: Dict[str, jax.Array], cfg) -> Dict:
    from repro.models import decoder

    tree = {
        "embed": {"table": w["tok"]},
        "seg0": {"pos0": {
            "mixer_norm": {"scale": w["ln_attn"]},
            "mixer": {"wq": w["q"], "wk": w["k"], "wv": w["v"],
                      "wo": w["o"]},
            "ffn_norm": {"scale": w["ln_ff"]},
            "ffn": {"wg": w["gate"], "w1": w["up"], "w2": w["down"]},
        }},
        "final_norm": {"scale": w["ln_out"]},
    }
    want = decoder.abstract_params(cfg)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError(f"{cfg.name}: not the program's parameter tree")
    return tree


def _logits(s: ModelSpec, w, tokens, low: bool):
    """[T, V] float32 logits; ``low``: bf16 matmul inputs (the control)."""
    T = tokens.shape[0]
    act, prec = (jnp.bfloat16, None) if low else (jnp.float32, HI)

    def mm(eq, a, b):
        return jnp.einsum(eq, a.astype(act), b.astype(act), precision=prec,
                          preferred_element_type=jnp.float32)

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + s.eps) * scale

    def rope(x):
        half = x.shape[-1] // 2
        ang = pos.astype(jnp.float32)[:, None] * s.theta ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    pos = jnp.arange(T, dtype=jnp.int32)
    causal = pos[None, :] <= pos[:, None]
    G = s.heads // s.groups
    x = w["tok"][tokens] * jnp.float32(s.width ** 0.5)

    def layer(x, lw):
        h = rms(x, lw["ln_attn"])
        q = rope(mm("td,dnk->tnk", h, lw["q"])).reshape(
            T, s.groups, G, s.hsize)
        kk = rope(mm("td,dnk->tnk", h, lw["k"]))
        v = mm("td,dnk->tnk", h, lw["v"])
        sc = mm("tngk,snk->ngts", q, kk) / math.sqrt(s.hsize)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        ctx = mm("ngts,snk->tngk", p, v).reshape(T, s.heads, s.hsize)
        x = x + mm("tnk,nkd->td", ctx, lw["o"])
        h = rms(x, lw["ln_ff"])
        z = jax.nn.silu(mm("td,df->tf", h, lw["gate"])) \
            * mm("td,df->tf", h, lw["up"])
        return x + mm("tf,fd->td", z, lw["down"]), None

    stacked = {n: w[n] for n in ("ln_attn", "ln_ff", "q", "k", "v", "o",
                                 "gate", "up", "down")}
    x, _ = jax.lax.scan(layer, x, stacked)
    return mm("td,vd->tv", rms(x, w["ln_out"]), w["tok"])


@partial(jax.jit, static_argnames=("s", "k", "control"))
def gaps(s: ModelSpec, w, tokens, n_prompt, n_total, k=None,
         control: bool = False):
    """As the Llama yardstick's ``gaps``, at tier 1.0 alone."""
    if k is not None:
        raise ValueError("the tied toy has no GRIFFIN reference")
    ref = _logits(s, w, tokens, low=False)
    t = jnp.arange(tokens.shape[0])
    on = (t >= n_prompt - 1) & (t <= n_total - 2)
    best = jnp.max(ref, -1)

    def gap(pick):
        g = best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
        return jnp.where(on, g, 0.0)

    served = gap(jnp.concatenate([tokens[1:], tokens[:1]]))
    if not control:
        return served, jnp.zeros_like(served)
    return served, gap(jnp.argmax(_logits(s, w, tokens, low=True), -1))


def keep(s: ModelSpec, tier: float) -> int:
    return max(1, min(s.ff, int(round(s.ff * tier))))


def token_flops(s: ModelSpec, ctx: int, k: int) -> float:
    D, hs = s.width, s.hsize
    proj = 2 * D * hs * (s.heads + s.groups)
    per_layer = 2 * (proj + 3 * D * k) + 4 * s.heads * hs * ctx
    return s.layers * per_layer + 2 * D * s.vocab


KERNEL_COUNTS: Dict[str, Any] = {}
