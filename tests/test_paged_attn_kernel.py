"""Fused paged-attention kernel (kernels/paged_attn.py) validation.

Two layers of evidence:

1. **Differential fuzz vs the gather-then-attend oracle** at fp32
   (``kernels/ref.py::paged_attn_ref`` — the same math as
   ``attention.paged_attn_step``'s fallback): random per-request
   lengths, GQA ratios, ``S ∈ {1, spec_k+1, chunk}``, ``global`` and
   ``local`` kinds, masked rows whose writes the oracle redirects to
   the trash page.  Context outputs agree to fp32 rounding and the
   *real* pages (everything but the trash page) stay bit-identical —
   the fused kernel never writes trash, so the trash page itself is
   exempt (no reader ever attends it).
2. **End-to-end token identity on the trained tiny model**: a
   ``PagedServer`` with ``kernel_backend="fused"`` emits exactly the
   tokens the ``gather`` oracle server emits, through preemption,
   prefix-cache hits, and ``spec_k ∈ {0, 4}``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import GriffinConfig
from repro.kernels import ops
from repro.models import decoder
from repro.models.layers import attention as attn_lib
from repro.serving.server import PagedServer


def _mk_case(rng, B, S, H, KV, hd, page, W, window,
             pool_dtype="float32"):
    """Random paged-attention inputs with prefix-allocated tables."""
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    lens = rng.integers(0, (W - 1) * page - S, size=B)
    need = [-(-(int(l) + S) // page) for l in lens]
    P = sum(need) + 2
    pk = jnp.asarray(rng.normal(size=(P + 1, KV, page, hd)),
                     jnp.float32).astype(pool_dtype)
    pv = jnp.asarray(rng.normal(size=(P + 1, KV, page, hd)),
                     jnp.float32).astype(pool_dtype)
    bt = np.full((B, W), -1, np.int32)
    perm = rng.permutation(P)
    c = 0
    for b in range(B):
        bt[b, : need[b]] = perm[c : c + need[b]]
        c += need[b]
    wm = rng.random((B, S)) > 0.25
    # at least one fully-masked row exercises the inactive-slot path
    if B > 1:
        wm[-1] = False
    return (q, kn, vn, pk, pv, jnp.asarray(bt),
            jnp.asarray(lens.astype(np.int32)), jnp.asarray(wm))


@pytest.mark.parametrize("B,S,H,KV,hd,page,W,window", [
    (3, 1, 4, 2, 8, 4, 8, 0),      # vanilla decode, GQA 2:1
    (4, 1, 4, 1, 16, 8, 6, 0),     # MQA
    (2, 5, 6, 3, 16, 8, 8, 0),     # speculative verify rows (spec_k=4)
    (2, 5, 4, 4, 8, 4, 12, 5),     # MHA + sliding window
    (1, 32, 6, 3, 32, 16, 8, 0),   # prefill chunk spanning pages
    (2, 3, 8, 2, 8, 4, 10, 6),     # window smaller than context
])
def test_fused_matches_oracle(B, S, H, KV, hd, page, W, window):
    rng = np.random.default_rng(B * 1000 + S * 10 + W + window)
    args = _mk_case(rng, B, S, H, KV, hd, page, W, window)
    ctx_f, pk_f, pv_f = ops.paged_attention(*args, window=window)
    ctx_r, pk_r, pv_r = ops.paged_attn_ref(*args, window=window)
    wm = np.asarray(args[7])
    rows = wm.any(axis=1)  # fully-inactive rows are garbage on both paths
    np.testing.assert_allclose(
        np.asarray(ctx_f)[rows], np.asarray(ctx_r)[rows],
        rtol=1e-5, atol=1e-5,
    )
    # real pages bit-identical; trash page exempt (fused never writes it)
    np.testing.assert_array_equal(np.asarray(pk_f)[:-1], np.asarray(pk_r)[:-1])
    np.testing.assert_array_equal(np.asarray(pv_f)[:-1], np.asarray(pv_r)[:-1])


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
def test_fused_matches_oracle_fuzz(pool_dtype):
    # bf16 pools: the scatter rounds rows to bf16 identically on both
    # paths and the attend upcasts the same stored bits to fp32, so
    # pages stay bit-identical and ctx keeps the fp32 tolerance
    rng = np.random.default_rng(7)
    for trial in range(8):
        KV = int(rng.choice([1, 2, 3]))
        G = int(rng.choice([1, 2, 4]))
        S = int(rng.choice([1, 2, 5]))
        page = int(rng.choice([4, 8]))
        case = _mk_case(rng, B=int(rng.integers(1, 5)), S=S, H=KV * G,
                        KV=KV, hd=8, page=page,
                        W=int(rng.integers(3, 10)), window=0,
                        pool_dtype=pool_dtype)
        window = int(rng.choice([0, 3, 9]))
        ctx_f, pk_f, pv_f = ops.paged_attention(*case, window=window)
        ctx_r, pk_r, pv_r = ops.paged_attn_ref(*case, window=window)
        assert pk_f.dtype == jnp.dtype(pool_dtype)
        wm = np.asarray(case[7])
        rows = wm.any(axis=1)
        np.testing.assert_allclose(
            np.asarray(ctx_f)[rows], np.asarray(ctx_r)[rows],
            rtol=1e-5, atol=1e-5, err_msg=f"trial {trial}",
        )
        np.testing.assert_array_equal(
            np.asarray(pk_f, dtype=np.float32)[:-1],
            np.asarray(pk_r, dtype=np.float32)[:-1],
        )


def test_inactive_slot_never_touches_real_pages():
    """A row with no allocated pages (inactive decode slot: bt all -1,
    write_mask false) must leave every real page bit-identical — its
    clamped page index maps to the trash page, not page 0 (regression:
    an unconditional block write-back through page 0 would race that
    page's real owner on compiled TPU runs)."""
    rng = np.random.default_rng(11)
    B, S, H, KV, hd, page, W = 3, 1, 4, 2, 8, 4, 6
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    P = 6
    pk = jnp.asarray(rng.normal(size=(P + 1, KV, page, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(P + 1, KV, page, hd)), jnp.float32)
    bt = np.full((B, W), -1, np.int32)
    bt[0, :2] = [3, 0]   # active request WRITES page 0 (pos 5 -> page 1...
    pos = np.asarray([5, 0, 0], np.int32)  # req 0 writes page bt[0,1]=0
    wm = np.asarray([[True], [False], [False]])  # rows 1, 2 inactive
    ctx_f, pk_f, pv_f = ops.paged_attention(
        q, kn, vn, pk, pv, jnp.asarray(bt), jnp.asarray(pos),
        jnp.asarray(wm))
    ctx_r, pk_r, pv_r = ops.paged_attn_ref(
        q, kn, vn, pk, pv, jnp.asarray(bt), jnp.asarray(pos),
        jnp.asarray(wm))
    np.testing.assert_allclose(np.asarray(ctx_f)[:1], np.asarray(ctx_r)[:1],
                               rtol=1e-5, atol=1e-5)
    # page 0 holds req 0's new token and nothing else; pages 1-5 untouched
    np.testing.assert_array_equal(np.asarray(pk_f)[:-1],
                                  np.asarray(pk_r)[:-1])
    np.testing.assert_array_equal(np.asarray(pv_f)[:-1],
                                  np.asarray(pv_r)[:-1])


def test_paged_attn_step_backend_parity():
    """Full layer step (projection + scatter + attend + out-proj):
    fused vs gather on random params."""
    cfg = get_config("tinylm")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    lp = params["seg0"]["pos0"]  # stacked [n_layers, ...]; take layer 0
    mixer = jax.tree.map(lambda v: v[0], lp["mixer"])
    rng = np.random.default_rng(3)
    B, S, page, W, P = 3, 2, 8, 6, 12
    x = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)), jnp.float32)
    pool = {
        "k": jnp.asarray(rng.normal(
            size=(P + 1, cfg.num_kv_heads, page, cfg.head_dim)), jnp.float32),
        "v": jnp.asarray(rng.normal(
            size=(P + 1, cfg.num_kv_heads, page, cfg.head_dim)), jnp.float32),
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
        jnp.asarray(wm), cfg, backend="gather")
    y_f, pool_f = attn_lib.paged_attn_step(
        mixer, pool, jnp.asarray(bt), x, jnp.asarray(pos),
        jnp.asarray(wm), cfg, backend="fused")
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_g),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(pool_f["k"])[:-1],
                                  np.asarray(pool_g["k"])[:-1])


def test_resolve_backend_and_interpret_defaults():
    from repro.kernels.backend import default_interpret, resolve_interpret

    on_tpu = jax.default_backend() == "tpu"
    assert default_interpret() == (not on_tpu)
    assert resolve_interpret(None) == (not on_tpu)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    expect_auto = "fused" if on_tpu else "gather"
    assert attn_lib.resolve_attn_backend("auto") == expect_auto
    assert attn_lib.resolve_attn_backend("fused") == "fused"
    assert attn_lib.resolve_attn_backend("gather") == "gather"


# ---------------------------------------------------------------------------
# End-to-end: fused serving is token-identical to the oracle serving
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained():
    from benchmarks.common import trained_tiny

    return trained_tiny(steps=120)


def _serve(cfg, params, backend, prompts, *, spec_k, num_pages,
           prefix_cache):
    srv = PagedServer(
        cfg, params,
        gcfg=GriffinConfig(sparsity=0.5, per_shard_topk=False),
        page_size=8, num_pages=num_pages, n_slots=4, prefill_chunk=16,
        max_len=96, spec_k=spec_k, prefix_cache=prefix_cache,
        kernel_backend=backend,
    )
    for i, (p, g, prio) in enumerate(prompts):
        srv.submit(p, max_new=g, rid=i, priority=prio)
    return srv.drain(), srv.metrics.summary()


@pytest.mark.parametrize("spec_k,num_pages,prefix_cache", [
    (0, 96, False),   # plain decode, no pressure
    (0, 18, False),   # pool pressure -> preemption
    (4, 96, True),    # speculative + prefix hits
    (4, 30, True),    # speculative under pressure
])
def test_e2e_fused_token_identical(trained, spec_k, num_pages,
                                   prefix_cache):
    cfg, params = trained
    from repro.data.pipeline import SyntheticCorpus

    corpus = SyntheticCorpus(vocab=cfg.vocab_size, seed=0)
    rng = np.random.default_rng(42 + spec_k + num_pages)
    shared = corpus.sample(32, seed=31)  # repeated head -> prefix hits
    prompts = []
    for i in range(7):
        if prefix_cache and i % 2 == 0:
            p = np.concatenate(
                [shared, corpus.sample(int(rng.integers(4, 12)),
                                       seed=600 + i)])
        else:
            p = corpus.sample(int(rng.integers(16, 56)), seed=700 + i)
        prompts.append((p, int(rng.integers(6, 14)), i % 2))

    out_g, m_g = _serve(cfg, params, "gather", prompts, spec_k=spec_k,
                        num_pages=num_pages, prefix_cache=prefix_cache)
    out_f, m_f = _serve(cfg, params, "fused", prompts, spec_k=spec_k,
                        num_pages=num_pages, prefix_cache=prefix_cache)
    assert out_f == out_g
    assert m_f["generated_tokens"] == m_g["generated_tokens"]
    # the whole point: the fused path models strictly less attention
    # HBM traffic than the oracle's full-width gather
    assert 0 < m_f["attn_bytes_read_total"] < m_g["attn_bytes_read_total"]
    if prefix_cache:
        assert m_f["prefix_hit_rate"] > 0
    if num_pages <= 20 and spec_k == 0:
        assert m_g["preemptions"] > 0  # the pressure case really preempts
