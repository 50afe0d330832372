"""Per-layer sparsity profiles served as per-request tiers.

Four layers of coverage, mirroring the refactor's guarantees:

* unit — ``resolve_tier``/``tier_k`` budgets, ``SparsityProfile``
  round-trip + validation, ``plan_k_tree`` per-layer widths,
* selector/compaction grid — ``pad_selection`` padding and the
  ``compaction_shards`` predicate across
  ``mode ∈ {topk, sampling, blocks}`` × ``tp_shards`` × per-layer ``k``,
* end-to-end — tier=1.0 ≡ dense oracle, tier=0.5 uniform ≡ legacy
  global sparsity=0.5 (through preemption and spec_k ∈ {0, 4}), and
  every stream of a mixed-tier batch ≡ its single-tier run,
* wire — tier threading through SLO classes and the frontend.

The 8-device TP variants live in ``distributed_progs/prog_tier_parity``
(subprocess, same pattern as ``test_sharded_serving``).
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import griffin as griffin_lib
from repro.core import (
    GriffinConfig,
    SparsityProfile,
    TIERS,
    plan_k_tree,
    resolve_tier,
    select_and_compact,
    select_experts,
    tier_k,
)
from repro.core import selector as selector_lib
from repro.models import decoder
from repro.serving.server import PagedServer
from repro.serving.slo import SLOClass

PROGS = Path(__file__).parent / "distributed_progs"
SRC = str(Path(__file__).parent.parent / "src")


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinylm")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# Tiers and budgets
# ---------------------------------------------------------------------------

def test_resolve_tier():
    assert resolve_tier(None) is None
    for t in TIERS:
        assert resolve_tier(t) == t
    assert resolve_tier(0.25 + 1e-12) == 0.25  # float-noise tolerant
    for bad in (0.3, 0.0, 1.5, -0.5):
        with pytest.raises(ValueError):
            resolve_tier(bad)


def test_tier_k_budgets():
    assert tier_k(512, 1.0) == 512
    assert tier_k(512, 0.5) == 256
    assert tier_k(512, 0.25) == 128
    # profile weight scales the budget; clamp to [1, d_ff]
    assert tier_k(512, 0.5, weight=1.5) == 384
    assert tier_k(512, 0.25, weight=0.001) == 1
    assert tier_k(512, 1.0, weight=1.5) == 512
    # per-layer divisible-k_ff rule: round *up* to a tp_shards multiple
    assert tier_k(512, 0.25, weight=1.1, tp_shards=16) == 144  # 140.8 -> 144
    assert tier_k(512, 0.25, tp_shards=16) == 128  # already divisible


def test_profile_roundtrip_and_validation(tmp_path):
    p = SparsityProfile(
        weights=(("seg0/pos0", (1.2, 0.8)), ("seg1/layer0", (1.0,))),
        arch="tinylm", note="test",
    )
    dest = tmp_path / "prof.json"
    p.save(dest)
    q = SparsityProfile.load(dest)
    assert q == p
    assert q.weights_for("seg0/pos0", 2) == (1.2, 0.8)
    # unknown path -> flat weights (profile-less behavior)
    assert q.weights_for("seg9/layer9", 3) == (1.0, 1.0, 1.0)
    with pytest.raises(ValueError):  # instance-count mismatch
        q.weights_for("seg0/pos0", 4)
    with pytest.raises(ValueError):  # weights must be > 0
        SparsityProfile(weights=(("a", (0.0,)),))


def test_plan_k_tree_per_layer(tiny):
    cfg, _ = tiny
    F = cfg.d_ff
    widths = griffin_lib.ffn_widths(cfg)
    assert widths, "tinylm must expose prunable FF layers"

    # legacy: every layer gets the global budget
    gcfg = GriffinConfig(sparsity=0.5)
    ks = plan_k_tree(cfg, gcfg)
    assert set(ks) == set(widths)
    for path, (n, f) in widths.items():
        assert ks[path] == (gcfg.k_of(f),) * n

    # tier, profiled: per-instance budgets follow the weights
    (path0, (n0, _)), = list(widths.items())[:1]
    w = tuple(0.8 + 0.1 * i for i in range(n0))
    prof = SparsityProfile(weights=((path0, w),))
    ks = plan_k_tree(cfg, gcfg, tier=0.5, profile=prof)
    assert ks[path0] == tuple(tier_k(F, 0.5, wi) for wi in w)
    assert len(set(ks[path0])) > 1, "per-instance budgets must differ"

    # tp rule holds per layer
    gcfg8 = GriffinConfig(sparsity=0.5, tp_shards=8)
    for kk in plan_k_tree(cfg, gcfg8, tier=0.25, profile=prof).values():
        assert all(k % 8 == 0 for k in kk)

    # blocks mode returns the widths the selector actually produces
    gcfg_b = GriffinConfig(sparsity=0.5, mode="blocks", block_size=32)
    for path, kk in plan_k_tree(cfg, gcfg_b, tier=0.5).items():
        assert all(k % 32 == 0 for k in kk)


# ---------------------------------------------------------------------------
# Selector / compaction grid (mode × shards × k)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["topk", "sampling", "blocks"])
@pytest.mark.parametrize("shards", [1, 2])
def test_selection_padding_grid(mode, shards):
    """pad_selection keeps real experts, marks dead rows, and respects
    the per-shard interleaved layout."""
    F, k = 64, 16
    gcfg = GriffinConfig(sparsity=0.5, mode=mode, block_size=8,
                         tp_shards=shards)
    rng = np.random.default_rng(0)
    s_sq = jnp.asarray(rng.random((4, F)), jnp.float32)
    idx = select_experts(s_sq, gcfg, rng=jax.random.PRNGKey(0), k=k)
    width = selector_lib.selected_width(mode, k, F, gcfg.block_size)
    assert idx.shape == (width,)

    k_pad = 2 * width
    sh = griffin_lib.compaction_shards(gcfg, width, F)
    idx_p, keep = selector_lib.pad_selection(idx, k_pad, F, shards=sh)
    assert idx_p.shape == (k_pad,) and keep.shape == (k_pad,)
    assert int(keep.sum()) == width
    # every originally selected expert survives in the padded set
    assert set(np.asarray(idx).tolist()) <= set(
        np.asarray(idx_p)[np.asarray(keep) > 0].tolist())
    if sh > 1:
        # interleaved: each shard block keeps exactly width/sh live rows
        assert width % sh == 0 and k_pad % sh == 0
        keep_blocks = np.asarray(keep).reshape(sh, k_pad // sh)
        assert (keep_blocks.sum(axis=1) == width // sh).all()
        # padded indices stay inside their shard's F/sh range
        idx_blocks = np.asarray(idx_p).reshape(sh, k_pad // sh)
        fs = F // sh
        for s in range(sh):
            assert ((idx_blocks[s] >= s * fs) & (idx_blocks[s] < (s + 1) * fs)).all()


def test_compaction_shards_predicate():
    """Shard-local gather only for balanced per-shard topk; everything
    else (sampling/blocks/indivisible) falls back to the plain gather."""
    g = lambda **kw: GriffinConfig(sparsity=0.5, **kw)
    assert griffin_lib.compaction_shards(g(tp_shards=4, per_shard_topk=True), 16, 64) == 4
    assert griffin_lib.compaction_shards(g(tp_shards=1), 16, 64) == 1
    assert griffin_lib.compaction_shards(
        g(tp_shards=4, per_shard_topk=False), 16, 64) == 1
    assert griffin_lib.compaction_shards(
        g(tp_shards=4, mode="sampling"), 16, 64) == 1
    assert griffin_lib.compaction_shards(
        g(tp_shards=4, mode="blocks"), 16, 64) == 1
    assert griffin_lib.compaction_shards(g(tp_shards=4), 18, 64) == 1  # k % 4
    assert griffin_lib.compaction_shards(g(tp_shards=4), 16, 66) == 1  # F % 4


def test_select_and_compact_per_layer_ks(tiny):
    """The single entry point honors per-instance budgets: scan leaves
    pad to the widest instance, narrower instances carry dead rows."""
    cfg, params = tiny
    gcfg = GriffinConfig(sparsity=0.5)
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)), jnp.int32)
    _, aux = decoder.forward(params, cfg, toks, collect_stats=True,
                             remat=False, logits_mode="last")
    stats = decoder.prune_stats_tree(aux.stats, cfg)
    s_sq = jax.tree.map(lambda d: d["s_sq"], stats,
                        is_leaf=lambda x: isinstance(x, dict) and "s_sq" in x)
    ffn_tree = decoder.extract_ffn_tree(params, cfg)

    widths = griffin_lib.ffn_widths(cfg)
    path0 = next(iter(widths))
    n0, F = widths[path0]
    ks = {path0: tuple(F // 4 if i == 0 else F // 2 for i in range(n0))}
    pruned, out_w = select_and_compact(s_sq, ffn_tree, gcfg, ks=ks)
    expect = F // 2 if n0 > 1 else F // 4
    assert out_w[path0] == expect
    seg, name = path0.split("/")
    w2 = pruned[seg][name]["w2"]
    assert w2.shape[-2] == expect
    if n0 > 1:  # narrow instance rides with zeroed dead rows
        dead = np.asarray(w2[0, F // 4:])
        assert (dead == 0).all()
        assert np.abs(np.asarray(w2[0, :F // 4])).sum() > 0


# ---------------------------------------------------------------------------
# End-to-end server identities (single device)
# ---------------------------------------------------------------------------

def _serve(cfg, params, prompts, max_new, *, gcfg, tiers=None,
           default_tier=None, profile=None, spec_k=0, num_pages=32):
    srv = PagedServer(cfg, params, gcfg=gcfg, page_size=8,
                      num_pages=num_pages, n_slots=3, prefill_chunk=16,
                      max_len=64, spec_k=spec_k, profile=profile,
                      default_tier=default_tier)
    for i, p in enumerate(prompts):
        srv.submit(p, max_new, rid=i,
                   tier=None if tiers is None else tiers[i])
    return srv, srv.drain()


def _prompts(cfg, n=4, seed=7):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
    out = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, size=8)
                           .astype(np.int32)]),
           np.concatenate([shared, rng.integers(0, cfg.vocab_size, size=10)
                           .astype(np.int32)])]
    for _ in range(n - 2):
        out.append(rng.integers(0, cfg.vocab_size, size=24).astype(np.int32))
    return out


def test_tier_full_matches_dense_oracle(tiny):
    """tier=1.0 must run the literal dense program: token-identical to a
    server with GRIFFIN disabled entirely."""
    cfg, params = tiny
    prompts = _prompts(cfg)
    _, dense = _serve(cfg, params, prompts, 8, gcfg=None)
    srv, full = _serve(cfg, params, prompts, 8,
                       gcfg=GriffinConfig(sparsity=0.5),
                       tiers=[1.0] * len(prompts))
    assert full == dense
    assert srv.metrics.prefix_hits >= 1


@pytest.mark.parametrize("spec_k", [0, 4])
def test_tier_half_matches_legacy_global(tiny, spec_k):
    """tier=0.5 uniform ≡ the legacy global sparsity=0.5 path — through
    prefix hits, preemption (tight pool) and speculative decoding."""
    cfg, params = tiny
    prompts = _prompts(cfg)
    gcfg = GriffinConfig(sparsity=0.5)
    s1, legacy = _serve(cfg, params, prompts, 10, gcfg=gcfg, num_pages=10)
    s2, tiered = _serve(cfg, params, prompts, 10, gcfg=gcfg, num_pages=10,
                        tiers=[0.5] * len(prompts), spec_k=spec_k)
    if spec_k == 0:
        assert tiered == legacy
        assert s2.metrics.summary()["preemptions"] >= 1
    else:
        # spec drafts at the global budget; dense verify keeps argmax
        # tokens aligned with the non-spec run on this greedy trace
        s3, spec_legacy = _serve(cfg, params, prompts, 10, gcfg=gcfg,
                                 num_pages=10, spec_k=spec_k)
        assert tiered == spec_legacy
        assert s2.metrics.summary()["spec_rounds"] >= 1
    assert s1.metrics.prefix_hits >= 1 and s2.metrics.prefix_hits >= 1


def test_default_tier_applies_to_untiered_requests(tiny):
    """Server-level default_tier covers submits with tier=None."""
    cfg, params = tiny
    prompts = _prompts(cfg, n=2)
    gcfg = GriffinConfig(sparsity=0.5)
    _, explicit = _serve(cfg, params, prompts, 6, gcfg=gcfg,
                         tiers=[0.25] * 2)
    _, defaulted = _serve(cfg, params, prompts, 6, gcfg=gcfg,
                          default_tier=0.25)
    assert explicit == defaulted


def test_mixed_tier_batch_matches_single_tier_runs(tiny):
    """Each stream of a mixed-tier batch (one tick, split dispatch, one
    union of both compacted tiers) is identical to running that request
    alone."""
    cfg, params = tiny
    prompts = _prompts(cfg, n=3, seed=9)
    gcfg = GriffinConfig(sparsity=0.5)
    tiers = [0.25, 0.5, 1.0]
    srv, mixed = _serve(cfg, params, prompts, 8, gcfg=gcfg, tiers=tiers)
    for i, t in enumerate(tiers):
        _, solo = _serve(cfg, params, [prompts[i]], 8, gcfg=gcfg, tiers=[t])
        assert mixed[i] == solo[0], f"rid={i} tier={t} diverged"


def test_tiered_server_with_profile_runs_and_tracks_widths(tiny):
    """A non-flat profile changes per-layer widths but still drains; each
    request's selection keeps every instance's own budget."""
    cfg, params = tiny
    widths = griffin_lib.ffn_widths(cfg)
    path0 = next(iter(widths))
    n0, F = widths[path0]
    prof = SparsityProfile(
        weights=((path0, tuple(1.3 if i % 2 else 0.7 for i in range(n0))),))
    gcfg = GriffinConfig(sparsity=0.5)
    ks = plan_k_tree(cfg, gcfg, tier=0.5, profile=prof)[path0]
    assert len(set(ks)) > 1

    srv = PagedServer(cfg, params, gcfg=gcfg, page_size=8, num_pages=32,
                      n_slots=2, prefill_chunk=16, max_len=64,
                      profile=prof, default_tier=0.5)
    prompts = _prompts(cfg, n=2)
    for i, p in enumerate(prompts):
        srv.submit(p, 6, rid=i)
    widths = []
    while srv.step():
        widths += [[len(s) for s in r.selection[path0]]
                   for r in srv.sched.decoding]
    assert widths and all(w == list(ks) for w in widths)
    out = srv.drain()
    assert all(len(v) == 6 for v in out.values())


def test_tier_requires_gcfg(tiny):
    cfg, params = tiny
    srv = PagedServer(cfg, params, gcfg=None, page_size=8, num_pages=16,
                      n_slots=2, prefill_chunk=16, max_len=64)
    with pytest.raises(ValueError, match="gcfg"):
        srv.submit(np.zeros(8, np.int32), 4, rid=0, tier=0.5)
    with pytest.raises(ValueError):
        PagedServer(cfg, params, gcfg=None, page_size=8, num_pages=16,
                    n_slots=2, prefill_chunk=16, max_len=64,
                    default_tier=0.5)


# ---------------------------------------------------------------------------
# Wire: SLO classes and frontend
# ---------------------------------------------------------------------------

def test_slo_class_tier_validation():
    c = SLOClass("cheap", priority=0, ttft_deadline_s=None, tier=0.25)
    assert c.tier == 0.25
    assert SLOClass("x", 0, None).tier is None
    with pytest.raises(ValueError):
        SLOClass("bad", priority=0, ttft_deadline_s=None, tier=0.33)


def test_frontend_threads_tier(tiny):
    from repro.serving.frontend import RequestRejected, ServingFrontend

    cfg, params = tiny
    srv = PagedServer(cfg, params, gcfg=GriffinConfig(sparsity=0.5),
                      page_size=8, num_pages=32, n_slots=2,
                      prefill_chunk=16, max_len=64)
    fe = ServingFrontend(srv)
    h = fe.submit(np.zeros(8, np.int32), 4, tier=0.25)
    assert h.slo.tier == 0.25
    with pytest.raises(RequestRejected):
        fe.submit(np.zeros(8, np.int32), 4, tier=0.33)

    dense = PagedServer(cfg, params, gcfg=None, page_size=8, num_pages=32,
                        n_slots=2, prefill_chunk=16, max_len=64)
    fe2 = ServingFrontend(dense)
    with pytest.raises(RequestRejected, match="GRIFFIN"):
        fe2.submit(np.zeros(8, np.int32), 4, tier=0.5)


# ---------------------------------------------------------------------------
# Profile derivation (offline pass)
# ---------------------------------------------------------------------------

def test_derive_profile_shape_and_normalization(tiny):
    from repro.analysis.profile import derive_profile

    cfg, params = tiny
    rng = np.random.default_rng(3)
    seqs = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 48)), jnp.int32)
    prof = derive_profile(cfg, params, seqs)
    widths = griffin_lib.ffn_widths(cfg)
    assert {p for p, _ in prof.weights} == set(widths)
    flat = [w for _, ws in prof.weights for w in ws]
    assert all(0.5 <= w <= 1.5 for w in flat)
    assert prof.arch == cfg.name
    # plan through the serving path end to end
    ks = plan_k_tree(cfg, GriffinConfig(sparsity=0.5), tier=0.5,
                     profile=prof)
    for path, (n, F) in widths.items():
        assert len(ks[path]) == n and all(1 <= k <= F for k in ks[path])


# ---------------------------------------------------------------------------
# Tensor-parallel (8 emulated devices, subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tier_parity_under_tp():
    """tier=0.5 ≡ legacy, tier=1.0 ≡ dense, mixed ≡ solo — on the
    shard_mapped server over an emulated 8-device host platform."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, str(PROGS / "prog_tier_parity.py")],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    assert r.returncode == 0, (
        f"prog_tier_parity.py failed:\n"
        f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    )
    assert "OK" in r.stdout, r.stdout
