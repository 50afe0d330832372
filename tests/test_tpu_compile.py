"""Mosaic compiles of the serving kernels at published widths, for a
described (not attached) TPU v5e chip.

Interpret mode accepts block shapes the TPU compiler refuses (the last
two block dims must be whole or (8, 128)-aligned) and VMEM budgets it
cannot meet, so every interpret-mode test can pass while the served
kernel cannot run on the chip.  These tests lower and compile the
kernels of the paged serving path for a ``v5e:2x2`` topology described
by the installed TPU compiler; nothing runs, so they say nothing about
results or time.

The topology is described inside a module-scoped fixture — never at
import — because only one process may hold the TPU library at a time:
under pytest-xdist only the worker that runs this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.griffin_ffn import griffin_ffn
from repro.kernels.paged_attn import paged_attn
from repro.kernels.paged_gather import paged_gather

PAGE = 16
NUM_PAGES = 256  # pool pages (+1 trash page)
WIDTH = 32       # block-table width: 512 tokens of context
BATCH = 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _attn_args(cfg, S, pool_dtype, sharding):
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pool = (NUM_PAGES + 1, KV, PAGE, hd)
    args = [
        _sds((BATCH, S, H, hd), cfg.dtype, sharding),
        _sds((BATCH, S, KV, hd), cfg.dtype, sharding),
        _sds((BATCH, S, KV, hd), cfg.dtype, sharding),
        _sds(pool, pool_dtype, sharding),
        _sds(pool, pool_dtype, sharding),
        _sds((BATCH, WIDTH), jnp.int32, sharding),
        _sds((BATCH,), jnp.int32, sharding),
        _sds((BATCH, S), jnp.bool_, sharding),
    ]
    scales = {}
    if pool_dtype == "int8":
        scale = _sds((NUM_PAGES + 1, KV, 1, 1), jnp.float32, sharding)
        scales = dict(scale_k=scale, scale_v=scale)
    return args, scales


def _compile_attn(cfg, S, kv_dtype, sharding):
    pool_dtype = "int8" if kv_dtype == "int8" else cfg.dtype
    args, scales = _attn_args(cfg, S, pool_dtype, sharding)

    def step(*a, **kw):
        return paged_attn(*a, **kw, kv_dtype=kv_dtype, interpret=False)

    compiled = jax.jit(step).lower(*args, **scales).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("arch,S,kv_dtype", [
    ("yi-9b", 1, "fp32"),          # decode, bf16 pool (model dtype)
    ("yi-9b", 32, "fp32"),         # prefill chunk
    ("yi-9b", 1, "int8"),          # quantized pool + scale pools
    ("smollm-360m", 1, "fp32"),    # hd 64, 5 KV heads, G = 3
])
def test_paged_attn_compiles(one_chip, arch, S, kv_dtype):
    _compile_attn(get_config(arch), S, kv_dtype, one_chip)


def test_paged_kv_gather_compiles(one_chip):
    cfg = get_config("yi-9b")
    pool = _sds((NUM_PAGES + 1, cfg.num_kv_heads, PAGE, cfg.head_dim),
                cfg.dtype, one_chip)
    bt = _sds((BATCH, WIDTH), jnp.int32, one_chip)

    def gather(pool, bt):
        return paged_gather(pool, bt, interpret=False)

    compiled = jax.jit(gather).lower(pool, bt).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [BATCH, 32])  # decode slots; a prefill chunk
def test_griffin_ffn_compiles(one_chip, rows):
    """The union FF kernel at yi-9b widths: an 8-layer stacked bf16
    buffer of capacity d_ff, 128-neuron blocks, a per-row mask."""
    cfg = get_config("yi-9b")
    L, C, D = 8, cfg.d_ff, cfg.d_model
    w = _sds((L, C, D), cfg.dtype, one_chip)
    args = (_sds((rows, D), cfg.dtype, one_chip), w, w, w,
            _sds((rows, C), jnp.float32, one_chip),
            _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip))

    def ffn(x, wg, w1, w2, mask, layer, n_live):
        return griffin_ffn(x, wg, w1, w2, mask, layer, n_live,
                           block_size=128, interpret=False)

    compiled = jax.jit(ffn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
