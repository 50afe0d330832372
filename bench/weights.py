"""Weights made on the device from the seed, in one jitted call.

The benchmark makes the weights, not the program: the reference reads
the plain stacked dict that :func:`make` returns, and the program gets
the same arrays rearranged into its own parameter tree
(:func:`program_params`), so the two start from identical bits and the
reference takes nothing the program has made.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.spec import ModelSpec


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of any size (64-bit seeds too)."""
    state = np.random.SeedSequence(int(seed) & (2**128 - 1)).generate_state(2)
    return jnp.asarray(state, dtype=jnp.uint32)


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
