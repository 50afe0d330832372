"""Tensor-parallel paged serving — identity + sharding-layout tests.

The end-to-end identity run (single-device vs shard_mapped server over
an emulated 8-device host platform) lives in a subprocess program, same
pattern as ``test_distributed.py``: the device-count flag must be set
before jax initializes and must never leak into the main test process.

The in-process tests below cover the host-side TP machinery that needs
no devices: the balanced divisible-``k_ff`` selection and the union-FF
PartitionSpec layout.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

PROGS = Path(__file__).parent / "distributed_progs"
SRC = str(Path(__file__).parent.parent / "src")


@pytest.mark.slow
def test_sharded_serving_token_identity():
    """Sharded serving (model axis 2 and 4) is token-identical to the
    single-device path through preemption, prefix hits, spec_k ∈ {0,4}
    and both attention backends; per-shard KV-pool bytes shrink 1/N."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, str(PROGS / "prog_sharded_serving.py")],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    assert r.returncode == 0, (
        f"prog_sharded_serving.py failed:\n"
        f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    )
    assert "OK" in r.stdout, r.stdout


def test_balanced_selection_pads_k_to_shards():
    """tp_shards rounds k up to a shard multiple and balances the pick:
    exactly k/N experts inside each contiguous F/N range."""
    from repro.core.griffin import GriffinConfig, select_experts

    F, shards = 1024, 16
    gcfg = GriffinConfig(sparsity=0.45, per_shard_topk=True,
                         tp_shards=shards)
    # naive k = round(1024 * 0.55) = 563 — not divisible by 16
    assert gcfg.k_of(F) == 576
    rng = np.random.default_rng(0)
    s_sq = rng.random((2, F)).astype(np.float32)
    idx = np.asarray(select_experts(np.asarray(s_sq), gcfg))
    assert idx.shape == (576,)
    per_shard = np.bincount(idx // (F // shards), minlength=shards)
    assert (per_shard == 576 // shards).all(), per_shard


def test_pruned_pspecs_shard_compacted_ffn():
    """The union FF tree shards along its neuron axis — buffers on the
    second dim, masks on the last, live counts one per shard — and
    rejects a capacity the mesh axis cannot divide (instead of silently
    replicating)."""
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.configs.registry import get_config
    from repro.distributed import tp as tp_lib

    cfg = get_config("tinylm-tp")
    # AbstractMesh: spec resolution needs axis names/sizes, no devices
    mesh = AbstractMesh((2,), ("model",))
    fac = tp_lib.PagedTP(cfg, mesh)
    D, C, L, B = cfg.d_model, 512, 4, 3
    z = np.zeros
    pruned = {
        "seg0": {
            "pos0": {
                "w1": z((L, C, D), np.float32),
                "wg": z((L, C, D), np.float32),
                "w2": z((L, C, D), np.float32),
                "mask": z((L, B, C), np.float32),
                "n_live": z((L, 2), np.int32),
            }
        }
    }
    specs = fac.pruned_pspecs(pruned)["seg0"]["pos0"]
    for k in ("w1", "wg", "w2"):
        assert specs[k] == P(None, "model")
    assert specs["mask"] == P(None, None, "model")
    assert specs["n_live"] == P(None, "model")


def test_pruned_pspecs_reject_indivisible_k():
    from jax.sharding import AbstractMesh

    from repro.configs.registry import get_config
    from repro.distributed import tp as tp_lib

    cfg = get_config("tinylm-tp")
    mesh = AbstractMesh((2,), ("model",))
    fac = tp_lib.PagedTP(cfg, mesh)
    pruned = {"seg0": {"pos0": {
        "w1": np.zeros((4, 255, cfg.d_model), np.float32),
    }}}
    with pytest.raises(ValueError, match="tp_shards"):
        fac.pruned_pspecs(pruned)
