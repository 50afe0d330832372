"""Page-boundary KV quantization (kernels/kv_quant.py) validation.

Three layers of evidence, mirroring test_paged_attn_kernel.py:

1. **Differential fuzz per quantized dtype**: the fused kernel and the
   plain-JAX oracle run the identical float program, so on int8/fp8
   pools the *pool bits* and the *scale pools* must stay bit-identical
   between them (real pages; the trash page is exempt) while contexts
   agree to fp32 rounding.  Against the **fp32 oracle**, quantized
   contexts stay inside the documented ``ERROR_BUDGET``.
2. **Monotone-scale property**: across sequential scatters scales never
   decrease, and rows written under an older (smaller) scale remain
   decodable within the per-element quantization step of the *new*
   scale (the re-encode never clips — DESIGN.md section 15).
3. **End-to-end on the trained tiny model**: an int8-pool server's
   greedy output token-matches an fp32-pool server at or above
   ``TOKEN_MATCH_FLOOR`` through preemption, prefix hits and
   ``spec_k ∈ {0, 4}``; fp32 and bf16 servers stay *exactly*
   token-identical.

Pool-plumbing coverage rides along: scale leaves in the cache specs,
``copy_pool_pages`` carrying scales with their pages, TP pspecs
sharding scales 1/N on the kv-head axis, and the byte accounting the
serving metrics and benchmarks share.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import GriffinConfig
from repro.kernels import kv_quant, ops
from repro.models import decoder
from repro.models.layers import attention as attn_lib
from repro.serving.server import PagedServer

QUANT = ["int8"] + (["fp8"] if hasattr(jnp, "float8_e4m3fn") else [])


def _mk_quant_case(rng, B, S, H, KV, hd, page, W, kvd):
    """Random decode inputs over *warm* quantized pools: an fp32 pool
    is quantized through the oracle scatter first, so every real page
    starts with live bits and a grown scale."""
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    lens = rng.integers(0, (W - 1) * page - S, size=B)
    need = [-(-(int(l) + S) // page) for l in lens]
    P = sum(need) + 2
    pkf = jnp.asarray(rng.normal(size=(P + 1, KV, page, hd)), jnp.float32)
    pvf = jnp.asarray(rng.normal(size=(P + 1, KV, page, hd)), jnp.float32)
    z = jnp.zeros((P + 1, KV, page, hd),
                  kv_quant.pool_jnp_dtype(kvd, "float32"))
    s0 = jnp.zeros((P + 1, KV, 1, 1), jnp.float32)
    gp = jnp.arange(P + 1).repeat(page)
    off = jnp.tile(jnp.arange(page), P + 1)

    def rows(pool):  # [P+1, KV, page, hd] -> [(P+1)*page, KV, hd]
        return pool.transpose(0, 2, 1, 3).reshape(-1, KV, hd)

    pk, sk = kv_quant.quantize_scatter_ref(z, s0, gp, off, rows(pkf), kvd)
    pv, sv = kv_quant.quantize_scatter_ref(z, s0, gp, off, rows(pvf), kvd)
    bt = np.full((B, W), -1, np.int32)
    perm = rng.permutation(P)
    c = 0
    for b in range(B):
        bt[b, : need[b]] = perm[c : c + need[b]]
        c += need[b]
    wm = rng.random((B, S)) > 0.25
    if B > 1:
        wm[-1] = False
    return ((q, kn, vn, pk, pv, jnp.asarray(bt),
             jnp.asarray(lens.astype(np.int32)), jnp.asarray(wm)),
            (pkf, pvf), (sk, sv))


@pytest.mark.parametrize("kvd", QUANT)
def test_fused_matches_quantized_oracle_fuzz(kvd):
    """Kernel vs same-dtype oracle: bits AND scales bit-identical on
    real pages, ctx to fp32 rounding (identical float program)."""
    rng = np.random.default_rng(13)
    for trial in range(6):
        KV = int(rng.choice([1, 2, 3]))
        G = int(rng.choice([1, 2, 4]))
        S = int(rng.choice([1, 2, 5]))
        page = int(rng.choice([4, 8]))
        case, _, (sk, sv) = _mk_quant_case(
            rng, B=int(rng.integers(1, 5)), S=S, H=KV * G, KV=KV, hd=8,
            page=page, W=int(rng.integers(3, 10)), kvd=kvd)
        outs = ops.paged_attention(*case, scale_k=sk, scale_v=sv,
                                   kv_dtype=kvd)
        refs = ops.paged_attn_ref(*case, scale_k=sk, scale_v=sv,
                                  kv_dtype=kvd)
        assert len(outs) == 5 and len(refs) == 5
        wm = np.asarray(case[7])
        rows = wm.any(axis=1)
        np.testing.assert_allclose(
            np.asarray(outs[0])[rows], np.asarray(refs[0])[rows],
            rtol=1e-5, atol=1e-5, err_msg=f"{kvd} trial {trial}")
        for i in (1, 2):  # pool bits
            np.testing.assert_array_equal(
                np.asarray(outs[i], dtype=np.float32)[:-1],
                np.asarray(refs[i], dtype=np.float32)[:-1],
                err_msg=f"{kvd} trial {trial} pool {i}")
        for i in (3, 4):  # scale pools
            np.testing.assert_array_equal(
                np.asarray(outs[i])[:-1], np.asarray(refs[i])[:-1],
                err_msg=f"{kvd} trial {trial} scale {i}")


@pytest.mark.parametrize("kvd", QUANT)
def test_quantized_ctx_within_error_budget(kvd):
    """Quantized kernel ctx vs the *fp32* oracle on the same underlying
    float pool: inside the documented ERROR_BUDGET."""
    rng = np.random.default_rng(17)
    worst = 0.0
    for trial in range(4):
        case, (pkf, pvf), (sk, sv) = _mk_quant_case(
            rng, B=2, S=1, H=4, KV=2, hd=16, page=8, W=6, kvd=kvd)
        q, kn, vn = case[0], case[1], case[2]
        fp32_case = (q, kn, vn, pkf, pvf) + case[5:]
        ctx_f = ops.paged_attn_ref(*fp32_case)[0]
        ctx_q = ops.paged_attention(*case, scale_k=sk, scale_v=sv,
                                    kv_dtype=kvd)[0]
        wm = np.asarray(case[7])
        rows = wm.any(axis=1)
        if not rows.any():
            continue
        err = float(np.abs(np.asarray(ctx_q)[rows]
                           - np.asarray(ctx_f)[rows]).max())
        worst = max(worst, err)
    assert 0 < worst <= kv_quant.ERROR_BUDGET[kvd], (kvd, worst)


@pytest.mark.parametrize("kvd", QUANT)
def test_monotone_scale_and_old_rows_stay_decodable(kvd):
    """Sequential scatters: scales never decrease, and a row written
    under the old scale still decodes within one quantization step of
    the *new* scale after growing data re-encodes the page."""
    rng = np.random.default_rng(19)
    KV, hd, page, P = 2, 8, 8, 4
    z = jnp.zeros((P + 1, KV, page, hd),
                  kv_quant.pool_jnp_dtype(kvd, "float32"))
    s = jnp.zeros((P + 1, KV, 1, 1), jnp.float32)
    pool = z
    written = {}  # (page, slot) -> fp32 row
    prev_s = np.asarray(s)
    for step, mag in enumerate((0.5, 1.0, 4.0, 16.0)):
        rows = jnp.asarray(rng.normal(size=(P, KV, hd)) * mag, jnp.float32)
        gp = jnp.arange(P)
        off = jnp.full((P,), step, jnp.int32)
        pool, s = kv_quant.quantize_scatter_ref(pool, s, gp, off, rows, kvd)
        cur_s = np.asarray(s)
        assert (cur_s >= prev_s).all(), f"{kvd} step {step}: scale shrank"
        prev_s = cur_s
        for p in range(P):
            written[(p, step)] = np.asarray(rows)[p]
        # every row ever written decodes within half a quantization
        # step of the *current* scale (re-encode cost, never clipped)
        dec = np.asarray(kv_quant.dequantize(
            pool, s))
        for (p, slot), orig in written.items():
            step_sz = np.maximum(cur_s[p, :, 0, 0], kv_quant.EPS)
            if kvd == "fp8":
                # fp8's step is relative (~6% per rounding) and each
                # scale growth re-encodes once more — bound loosely;
                # the property here is monotone/no-clip, not precision
                tol = np.abs(orig) * 0.25 + step_sz[:, None] * 0.5
            else:
                tol = np.broadcast_to(step_sz[:, None] * 1.01, orig.shape)
            assert (np.abs(dec[p, :, slot] - orig) <= tol).all(), (
                kvd, p, slot)


def test_identity_reencode_when_scale_unchanged():
    """A scatter that adds no rows to a page (amax 0) must leave its
    bits AND scale exactly unchanged — the property that makes the
    kernel's unconditional write-back benign for shared/COW pages."""
    rng = np.random.default_rng(23)
    for kvd in QUANT:
        KV, hd, page, P = 2, 8, 8, 4
        z = jnp.zeros((P + 1, KV, page, hd),
                      kv_quant.pool_jnp_dtype(kvd, "float32"))
        s0 = jnp.zeros((P + 1, KV, 1, 1), jnp.float32)
        rows = jnp.asarray(rng.normal(size=(P, KV, hd)), jnp.float32)
        pool, s = kv_quant.quantize_scatter_ref(
            z, s0, jnp.arange(P), jnp.zeros(P, jnp.int32), rows, kvd)
        # second scatter targets ONLY page 0: pages 1..P-1 see amax 0
        pool2, s2 = kv_quant.quantize_scatter_ref(
            pool, s, jnp.asarray([0]), jnp.asarray([1]),
            rows[:1] * 10.0, kvd)
        np.testing.assert_array_equal(
            np.asarray(pool2, dtype=np.float32)[1:-1],
            np.asarray(pool, dtype=np.float32)[1:-1])
        np.testing.assert_array_equal(np.asarray(s2)[1:-1],
                                      np.asarray(s)[1:-1])
        assert float(s2[0, 0, 0, 0]) >= float(s[0, 0, 0, 0])


def test_paged_attn_step_backend_parity_int8():
    """Full layer step on int8 pools: fused vs gather keep pool bits
    and scales bit-identical and outputs to fp32 rounding."""
    cfg = get_config("tinylm")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    lp = params["seg0"]["pos0"]
    mixer = jax.tree.map(lambda v: v[0], lp["mixer"])
    rng = np.random.default_rng(3)
    B, S, page, W, P = 3, 2, 8, 6, 12
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    x = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)), jnp.float32)
    pool = {
        "k": jnp.zeros((P + 1, KV, page, hd), jnp.int8),
        "v": jnp.zeros((P + 1, KV, page, hd), jnp.int8),
        "k_scale": jnp.zeros((P + 1, KV, 1, 1), jnp.float32),
        "v_scale": jnp.zeros((P + 1, KV, 1, 1), jnp.float32),
    }
    bt = np.full((B, W), -1, np.int32)
    pos = np.asarray([0, 9, 17], np.int32)
    c = 0
    for b in range(B):
        need = -(-(int(pos[b]) + S) // page)
        bt[b, :need] = np.arange(c, c + need)
        c += need
    wm = np.ones((B, S), bool)
    y_g, pool_g = attn_lib.paged_attn_step(
        mixer, pool, jnp.asarray(bt), x, jnp.asarray(pos),
        jnp.asarray(wm), cfg, backend="gather", kv_dtype="int8")
    y_f, pool_f = attn_lib.paged_attn_step(
        mixer, pool, jnp.asarray(bt), x, jnp.asarray(pos),
        jnp.asarray(wm), cfg, backend="fused", kv_dtype="int8")
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_g),
                               rtol=1e-5, atol=1e-5)
    for key in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(pool_f[key], dtype=np.float32)[:-1],
            np.asarray(pool_g[key], dtype=np.float32)[:-1],
            err_msg=key)
    assert pool_f["k"].dtype == jnp.int8


# ---------------------------------------------------------------------------
# Pool plumbing: specs, COW copies, byte accounting
# ---------------------------------------------------------------------------

def test_paged_cache_specs_scale_leaves():
    cfg = get_config("tinylm")
    for kvd in ("fp32", "bf16"):
        specs = attn_lib.paged_cache_specs(cfg, 8, 16, kvd)
        assert set(specs) == {"k", "v"}, kvd
    for kvd in QUANT:
        specs = attn_lib.paged_cache_specs(cfg, 8, 16, kvd)
        assert set(specs) == {"k", "v", "k_scale", "v_scale"}, kvd
        assert specs["k_scale"].shape == (9, cfg.num_kv_heads, 1, 1)
        assert specs["k_scale"].dtype == "float32"
        # scales shard with their pages/heads, replicate the unit axes
        assert specs["k_scale"].axes == ("pages", "kv_heads", None, None)
    pools = decoder.init_paged_pools(cfg, 8, 16, "int8")
    leaves = jax.tree.leaves(pools)
    dts = {str(x.dtype) for x in leaves}
    assert dts == {"int8", "float32"}, dts


def test_copy_pool_pages_carries_scales():
    """COW forks copy a page's scale rows with its data rows — a COW'd
    quantized page stays decodable without touching the source."""
    cfg = get_config("tinylm")
    pools = decoder.init_paged_pools(cfg, 8, 16, "int8")
    # write distinctive bits + scales into page 2 of every leaf
    pools = jax.tree.map(
        lambda p: p.at[..., 2, :, :, :].set(
            jnp.ones(p.shape[-3:], p.dtype)) if p.ndim >= 4 else p, pools)
    dst, src = jnp.asarray([5]), jnp.asarray([2])
    copied = decoder.copy_pool_pages(cfg, pools, src, dst)
    for leaf_c, leaf_o in zip(jax.tree.leaves(copied),
                              jax.tree.leaves(pools)):
        page_axis = 0 if leaf_c.ndim == 4 else 1
        got = np.take(np.asarray(leaf_c, dtype=np.float32), 5, page_axis)
        want = np.take(np.asarray(leaf_o, dtype=np.float32), 2, page_axis)
        np.testing.assert_array_equal(got, want)


def test_resolve_and_byte_accounting():
    with pytest.raises(ValueError, match="kv_dtype"):
        kv_quant.resolve_kv_dtype("int4")
    assert kv_quant.resolve_kv_dtype("int8") == "int8"
    assert not kv_quant.is_quantized("bf16")
    page, KV, hd = 16, 2, 64
    b32 = kv_quant.page_bytes(page, KV, hd, "fp32")
    b16 = kv_quant.page_bytes(page, KV, hd, "bf16")
    b8 = kv_quant.page_bytes(page, KV, hd, "int8")
    assert b32 == 2 * page * KV * hd * 4
    assert b16 == b32 // 2
    # int8 pays the scale rows on top of 1-byte elements
    assert b8 == 2 * page * KV * hd + 2 * KV * 4
    assert b32 / b8 > 3.9
    # fp32 inherits the model dtype: bf16 models store 2-byte pages
    assert kv_quant.page_bytes(page, KV, hd, "fp32", "bfloat16") == b16


def test_server_attn_bytes_use_pool_itemsize():
    """serving/metrics byte accounting (fed by _count_attn_bytes) must
    reflect the pool's actual itemsize + scale bytes, not the model
    dtype — int8 serving models ~4x fewer attention bytes/token."""
    cfg = get_config("tinylm")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (24, 40)]
    bpt = {}
    for kvd in ("fp32", "bf16", "int8"):
        srv = PagedServer(cfg, params, gcfg=None, page_size=16,
                          num_pages=64, n_slots=4, prefill_chunk=32,
                          max_len=128, kv_dtype=kvd)
        for i, p in enumerate(prompts):
            srv.submit(p, max_new=4, rid=i)
        srv.drain()
        bpt[kvd] = srv.metrics.summary()["attn_bytes_per_token"]
    assert bpt["bf16"] == pytest.approx(bpt["fp32"] / 2)
    # int8: 1/4 the data bytes plus the per-page scale rows
    assert bpt["fp32"] / 4 < bpt["int8"] < bpt["fp32"] / 3.5
    assert bpt["fp32"] / bpt["int8"] >= 1.9


# ---------------------------------------------------------------------------
# TP pspecs: scales shard 1/N on the kv-head axis (no devices needed)
# ---------------------------------------------------------------------------

def test_tp_pool_pspecs_shard_scales():
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.distributed import tp as tp_lib

    cfg = get_config("tinylm-tp")
    mesh = AbstractMesh((2,), ("model",))
    fac = tp_lib.PagedTP(cfg, mesh, kv_dtype="int8")
    specs = fac.pool_pspecs(num_pages=8, page_size=16)
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    # every leaf (data AND scale pools) shards kv_heads on the mesh axis
    assert len(flat) >= 4
    for spec in flat:
        assert "model" in spec, spec


# ---------------------------------------------------------------------------
# End-to-end: trained tiny model, quantized vs fp32 serving
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained():
    from benchmarks.common import trained_tiny

    return trained_tiny(steps=120)


def _serve(cfg, params, kv_dtype, prompts, *, spec_k, num_pages,
           prefix_cache):
    srv = PagedServer(
        cfg, params,
        gcfg=GriffinConfig(sparsity=0.5, per_shard_topk=False),
        page_size=8, num_pages=num_pages, n_slots=4, prefill_chunk=16,
        max_len=96, spec_k=spec_k, prefix_cache=prefix_cache,
        kv_dtype=kv_dtype,
    )
    for i, (p, g) in enumerate(prompts):
        srv.submit(p, max_new=g, rid=i)
    return srv.drain(), srv.metrics.summary()


@pytest.mark.slow
@pytest.mark.parametrize("spec_k,num_pages,prefix_cache", [
    (0, 18, False),   # pool pressure -> preemption
    (4, 96, True),    # speculative + prefix hits
])
def test_e2e_quantized_token_match(trained, spec_k, num_pages,
                                   prefix_cache):
    cfg, params = trained
    from repro.data.pipeline import SyntheticCorpus

    corpus = SyntheticCorpus(vocab=cfg.vocab_size, seed=0)
    rng = np.random.default_rng(42 + spec_k + num_pages)
    shared = corpus.sample(32, seed=31)
    prompts = []
    for i in range(6):
        if prefix_cache and i % 2 == 0:
            p = np.concatenate(
                [shared, corpus.sample(int(rng.integers(4, 12)),
                                       seed=600 + i)])
        else:
            p = corpus.sample(int(rng.integers(16, 56)), seed=700 + i)
        prompts.append((p, int(rng.integers(6, 14))))

    out_f, m_f = _serve(cfg, params, "fp32", prompts, spec_k=spec_k,
                        num_pages=num_pages, prefix_cache=prefix_cache)
    # bf16 rounds KV identically on scatter for every reader: in
    # practice token-identical on the tiny model (asserted exactly)
    out_b, _ = _serve(cfg, params, "bf16", prompts, spec_k=spec_k,
                      num_pages=num_pages, prefix_cache=prefix_cache)
    out_q, m_q = _serve(cfg, params, "int8", prompts, spec_k=spec_k,
                        num_pages=num_pages, prefix_cache=prefix_cache)
    assert out_b == out_f
    matched = total = 0
    for i in range(len(prompts)):
        a, b = out_f[i], out_q[i]
        matched += sum(x == y for x, y in zip(a, b))
        total += max(len(a), len(b))
    rate = matched / total
    assert rate >= kv_quant.TOKEN_MATCH_FLOOR["int8"], (
        f"int8 token match {rate:.3f} below floor "
        f"{kv_quant.TOKEN_MATCH_FLOOR['int8']} "
        f"(spec_k={spec_k}, num_pages={num_pages})")
    # quantized serving must model fewer attention bytes than fp32
    assert 0 < m_q["attn_bytes_read_total"] < m_f["attn_bytes_read_total"]
    if num_pages <= 20 and spec_k == 0:
        assert m_f["preemptions"] > 0
