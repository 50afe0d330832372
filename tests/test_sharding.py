"""Sharding-rule resolution unit tests (AbstractMesh — no devices)."""
import warnings

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import make_paged_tp_rules, make_rules, spec_for
from repro.models.param import ParamSpec


MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_basic_resolution():
    rules = make_rules(phase="train", fsdp=True)
    s = spec_for(("embed", "mlp"), rules, MESH1, (4096, 11008))
    assert s == P("data", "model")


def test_divisibility_drops_axis():
    rules = make_rules(phase="serve")
    # smollm: 15 heads can't shard 16 ways -> replicated
    s = spec_for(("embed", "heads", "head_dim"), rules, MESH1, (960, 15, 64))
    assert s == P()
    s = spec_for(("embed", "heads", "head_dim"), rules, MESH1, (4096, 32, 128))
    assert s == P(None, "model")


def test_axis_used_once():
    rules = make_rules(phase="serve", fsdp=True)
    # both embed->data; second occurrence must not reuse data
    s = spec_for(("embed", "embed"), rules, MESH1, (1024, 1024))
    assert s == P("data")


def test_batch_one_drops_to_kv_seq():
    rules = make_rules(phase="serve", kv_seq_model=True)
    # long_500k: batch=1 can't shard -> cache seq takes data AND model
    s = spec_for(("batch", "kv_seq", "kv_heads", "head_dim"), rules, MESH1,
                 (1, 524288, 4, 128))
    assert s == P(None, ("data", "model"))


def test_batch_grabs_pod_and_data_multipod():
    rules = make_rules(phase="train")
    s = spec_for(("batch", "seq"), rules, MESH2, (256, 4096))
    assert s == P(("pod", "data"))


def test_expert_2d():
    rules = make_rules(phase="train", expert_2d=True)
    s = spec_for(("experts", "embed", "mlp"), rules, MESH1, (256, 7168, 2048))
    assert s == P(("data", "model"))


def test_divisibility_drop_warns_once():
    """Dropping a mesh axis for divisibility is an N× memory regression
    in disguise — it must warn, exactly once per distinct drop."""
    from repro.distributed import sharding as shlib

    shlib._div_warned.clear()  # idempotent under pytest-repeat/reorder
    rules = make_rules(phase="serve")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        s = spec_for(("embed", "heads"), rules, MESH1, (4096, 17))
        assert s == P()  # 17 heads can't shard 16 ways
        drops = [x for x in w if "dropping mesh axis" in str(x.message)]
        assert len(drops) == 1
        assert "17" in str(drops[0].message)
        # identical drop again: already warned, stays quiet
        spec_for(("embed", "heads"), rules, MESH1, (4096, 17))
        drops = [x for x in w if "dropping mesh axis" in str(x.message)]
        assert len(drops) == 1


def test_compacted_ffn_stays_sharded_under_model_axis():
    """Regression (ISSUE 5): GRIFFIN compaction shrinks d_ff to k_ff;
    with tp_shards set, k_ff is padded to a shard multiple so the
    compacted FF weights keep their ``model``-axis sharding instead of
    silently replicating."""
    from repro.core.griffin import GriffinConfig

    rules = make_paged_tp_rules()
    F, D = 1024, 512
    gcfg = GriffinConfig(sparsity=0.45, tp_shards=16)
    k = gcfg.k_of(F)  # naive round(563.2) = 563 would drop the axis
    assert k % 16 == 0
    s = spec_for(("embed", "mlp"), rules, MESH1, (D, k))
    assert s == P(None, "model")
    s = spec_for(("mlp", "embed"), rules, MESH1, (k, D))
    assert s == P("model")
    # without the padding, the same width replicates (and warns)
    from repro.distributed import sharding as shlib

    shlib._div_warned.clear()  # idempotent under pytest-repeat/reorder
    naive_k = GriffinConfig(sparsity=0.45).k_of(F)
    with pytest.warns(UserWarning, match="dropping mesh axis"):
        s = spec_for(("embed", "mlp"), rules, MESH1, (D, naive_k))
    assert s == P()


def test_pruned_ffn_divisible_for_all_griffin_archs():
    """GRIFFIN k=50% widths must stay mlp-shardable on the 16-way TP axis."""
    from repro.configs.registry import ASSIGNED_ARCHS, get_config

    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        if not (cfg.griffin and cfg.has_ffn):
            continue
        widths = []
        if cfg.d_ff:
            widths.append(cfg.d_ff // 2)
        if cfg.num_experts and cfg.num_shared_experts:
            widths.append(cfg.moe_d_ff * cfg.num_shared_experts // 2)
        for k in widths:
            assert k % 16 == 0, (arch, k)
