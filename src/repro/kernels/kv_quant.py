"""Page-boundary KV quantization: the shared quantize/dequantize program.

The paged KV pools (``models/layers/attention.py::paged_cache_specs``)
can hold their bytes in four dtypes, selected by ``kv_dtype``:

* ``fp32`` — the pool inherits the model dtype (float32 on every
  serving config); bit-identical to the pre-quantization path.
* ``bf16`` — bfloat16 pages, no scales: the scatter rounds rows to
  bf16, attention upcasts to fp32.  Halves pool bytes.
* ``int8`` — int8 pages + a parallel fp32 *scale pool* (one scale per
  page per KV head, shape ``[P+1, KV, 1, 1]``), ~4x fewer pool bytes.
* ``fp8``  — float8_e4m3fn pages + the same scale pool (gated on the
  installed jax exposing ``jnp.float8_e4m3fn``).

Contract (DESIGN.md section 15): **only the attention kernel and its
oracle ever see quantized bytes.**  The allocator, prefix cache, COW
copies, pool donation, and TP sharding treat pages as opaque — the
scale pool is just another pool leaf addressed by the same page ids,
so ``decoder.copy_pool_pages``'s ``tree.map`` copies scales with their
pages and the ``("pages", "kv_heads", None, None)`` axes shard scale
bytes 1/N alongside the data.

The quantization program itself (identical float ops in the fused
Pallas kernel, the gather serving path, and ``kernels/ref.py``'s
oracle, so the three stay bit-identical on pool contents):

* per (page, kv_head) absmax scale, **monotone**: on scatter,
  ``s_new = max(s_old, absmax(new rows)/qmax)`` — the scale never
  shrinks, so re-encoding already-written rows only divides by a
  *larger* scale and can never clip;
* already-written rows are re-encoded under the new scale
  (``round(bits * s_old / s_new)``), which is exact when the scale did
  not change and costs at most one extra rounding when it grew;
* attention always runs in fp32 over ``bits * scale``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

KV_DTYPES = ("fp32", "bf16", "int8", "fp8")

#: guards division by a zero scale (page never written); any value far
#: below real activation scales works — both kernel and oracle must use
#: the same constant for bit parity
EPS = 1e-8

#: documented max absolute context error vs the fp32 oracle for
#: quantized pools on unit-Gaussian K/V (asserted by tests + CI smoke)
ERROR_BUDGET = {"int8": 0.05, "fp8": 0.12}

#: committed floor for greedy token-match rate vs an fp32-pool server
#: on the trained tiny model (CI smoke fails below it)
TOKEN_MATCH_FLOOR = {"int8": 0.85, "fp8": 0.80}


def resolve_kv_dtype(kv_dtype: str) -> str:
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
    if kv_dtype == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
        raise ValueError(
            "kv_dtype='fp8' needs a jax with float8_e4m3fn support; "
            "use 'int8' on this backend"
        )
    return kv_dtype


def is_quantized(kv_dtype: str) -> bool:
    return kv_dtype in ("int8", "fp8")


def pool_jnp_dtype(kv_dtype: str, model_dtype) -> jnp.dtype:
    """Concrete page dtype.  ``fp32`` inherits the model dtype (the
    pre-quantization behavior; float32 on every serving config)."""
    resolve_kv_dtype(kv_dtype)
    if kv_dtype == "fp32":
        return jnp.dtype(model_dtype)
    if kv_dtype == "bf16":
        return jnp.dtype(jnp.bfloat16)
    if kv_dtype == "int8":
        return jnp.dtype(jnp.int8)
    return jnp.dtype(jnp.float8_e4m3fn)


def qmax(kv_dtype: str) -> float:
    """Largest representable quantized magnitude (scale denominator)."""
    return {"int8": 127.0, "fp8": 448.0}[kv_dtype]


def quantize(x: jax.Array, s_eff: jax.Array, kv_dtype: str) -> jax.Array:
    """fp32 values -> quantized bits under (eps-guarded) scale ``s_eff``.

    ``s_eff >= absmax(x)/qmax`` by the monotone-scale construction, so
    the int8 clip never truncates real data and the fp8 cast never
    saturates; the clip only pins float round-off at the boundary.
    """
    v = x.astype(jnp.float32) / s_eff
    if kv_dtype == "int8":
        return jnp.clip(jnp.round(v), -127.0, 127.0).astype(jnp.int8)
    return v.astype(jnp.float8_e4m3fn)


def dequantize(bits: jax.Array, s: jax.Array) -> jax.Array:
    return bits.astype(jnp.float32) * s


def new_scale(s_old: jax.Array, amax_new: jax.Array, kv_dtype: str) -> jax.Array:
    """Monotone per-(page, head) scale update.

    Multiplies by the precomputed reciprocal rather than dividing:
    XLA strength-reduces division by a constant to a reciprocal
    multiply *inside jitted code* (the fused kernel) but not in eager
    ops (the oracle), and the two differ by 1 ulp.  Writing the
    multiply explicitly keeps kernel and oracle scales bit-identical.
    """
    return jnp.maximum(s_old, amax_new * (1.0 / qmax(kv_dtype)))


def quantize_scatter_ref(
    pool: jax.Array,    # [P+1, KV, page, hd] quantized bits
    scale: jax.Array,   # [P+1, KV, 1, 1] fp32
    gp: jax.Array,      # [N] int32 destination page per new row
    offset: jax.Array,  # [N] int32 slot within the page
    rows: jax.Array,    # [N, KV, hd] new rows (any float dtype)
    kv_dtype: str,
) -> Tuple[jax.Array, jax.Array]:
    """Plain-JAX quantized scatter (the oracle/gather-path side of the
    in-kernel program).  Re-encodes the whole pool under the updated
    scales — an exact no-op wherever the scale didn't change, and the
    same per-element float ops as the fused kernel wherever it did —
    then writes the new rows.  Returns (new pool bits, new scales).
    """
    rows_f = rows.astype(jnp.float32)
    P1, KV = pool.shape[:2]
    amax = jnp.zeros((P1, KV), jnp.float32).at[gp].max(
        jnp.max(jnp.abs(rows_f), axis=-1)
    )
    s_new = new_scale(scale[:, :, 0, 0], amax, kv_dtype)  # [P+1, KV]
    s_eff = jnp.maximum(s_new, EPS)
    old_f = dequantize(pool, scale)
    requant = quantize(old_f, s_eff[:, :, None, None], kv_dtype)
    new_bits = quantize(rows_f, s_eff[gp][:, :, None], kv_dtype)
    return (requant.at[gp, :, offset].set(new_bits),
            s_new[:, :, None, None])


def gather_scales(scale: jax.Array, block_tables: jax.Array,
                  page_size: int) -> jax.Array:
    """[P+1, KV, 1, 1] scales -> [B, n*page, KV, 1] aligned with the
    gathered page view (one scale repeated across a page's slots)."""
    s = jnp.take(scale[:, :, 0, 0], jnp.clip(block_tables, 0), axis=0)
    return jnp.repeat(s, page_size, axis=1)[..., None]


# ---------------------------------------------------------------------------
# Byte accounting (serving/metrics + benchmarks)
# ---------------------------------------------------------------------------

def kv_itemsize(kv_dtype: str, model_dtype) -> int:
    return pool_jnp_dtype(kv_dtype, model_dtype).itemsize


def scale_bytes_per_page(kv_dtype: str, kv_heads: int) -> int:
    """fp32 scale bytes one page carries across both scale pools."""
    return 2 * kv_heads * 4 if is_quantized(kv_dtype) else 0


def page_bytes(page_size: int, kv_heads: int, head_dim: int,
               kv_dtype: str, model_dtype="float32") -> int:
    """Total pool bytes one page occupies (K + V data + scales)."""
    data = 2 * page_size * kv_heads * head_dim * kv_itemsize(
        kv_dtype, model_dtype
    )
    return data + scale_bytes_per_page(kv_dtype, kv_heads)
