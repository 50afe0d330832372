"""Paged-KV serving subsystem tests: allocator invariants, paged-vs-
contiguous attention equivalence, chunked-prefill GRIFFIN statistic
equivalence, scheduler fairness/preemption, and end-to-end server-vs-
engine parity (GRIFFIN on and off)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import GriffinConfig
from repro.kernels import ops
from repro.models import decoder
from repro.serving.metrics import ServingMetrics
from repro.serving.paged import BlockAllocator, BlockTable, PagedConfig
from repro.serving.scheduler import DECODING, QUEUED, Scheduler
from repro.serving.server import PagedServer


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinylm")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# Block allocator
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_invariants():
    a = BlockAllocator(8)
    p1 = a.alloc(rid=1, n=3)
    p2 = a.alloc(rid=2, n=2)
    assert len(set(p1) | set(p2)) == 5  # no page handed out twice
    assert a.num_free == 3 and a.num_in_use == 5
    a.check()
    assert a.free_request(1) == 3
    assert a.num_free == 6
    assert a.pages_of(1) == [] and a.pages_of(2) == sorted(p2)
    a.check()


def test_allocator_all_or_nothing():
    a = BlockAllocator(4)
    a.alloc(rid=1, n=3)
    assert not a.can_alloc(2)
    with pytest.raises(MemoryError):
        a.alloc(rid=2, n=2)
    assert a.num_free == 1  # failed alloc leaks nothing
    a.check()


def test_block_table_growth():
    t = BlockTable()
    assert t.pages_needed(17, page_size=8) == 3
    t.pages.extend([5, 2, 9])
    assert t.pages_needed(17, page_size=8) == 0
    assert t.pages_needed(25, page_size=8) == 1
    bt = t.as_array(6)
    assert list(bt) == [5, 2, 9, -1, -1, -1]


# ---------------------------------------------------------------------------
# Paged vs contiguous attention equivalence
# ---------------------------------------------------------------------------

def _paged_prefill(cfg, params, pools, bt, toks, chunk):
    """Drive decode_step_paged chunk-wise over a [1, S] prompt."""
    S = toks.shape[1]
    last = None
    stats_acc = None
    for c0 in range(0, S, chunk):
        piece = toks[:, c0 : c0 + chunk]
        logits, pools, stats = decoder.decode_step_paged(
            params, cfg, pools, jnp.asarray(bt), piece,
            jnp.array([c0], np.int32), collect_stats=True,
        )
        last = logits
        part = decoder.prune_stats_tree(stats, cfg)
        stats_acc = part if stats_acc is None else jax.tree.map(
            jnp.add, stats_acc, part
        )
    return last, pools, stats_acc


def test_paged_decode_bitexact_vs_contiguous(tiny):
    """Paged decode logits match decoder.decode_step bit-for-bit (fp32)."""
    cfg, params = tiny
    rng = jax.random.PRNGKey(1)
    S, G, page, W = 24, 5, 8, 8
    toks = jax.random.randint(rng, (1, S + G), 0, cfg.vocab_size)

    ref_logits, aux = decoder.forward(params, cfg, toks[:, :S], want_kv=True,
                                      remat=False, logits_mode="last")
    cache = decoder.init_cache(cfg, 1, W * page)
    cache = decoder.fill_cache_from_prefill(cfg, cache, aux.kv)

    bt = np.full((1, W), -1, np.int32)
    need = -(-S // page)
    bt[0, :need] = np.arange(need)

    def written(pools):  # the K/V the paged prefill wrote, [L, 1, S, KV, hd]
        return jax.tree.map(
            lambda buf: jax.vmap(ops.paged_kv_gather_ref, (0, None))(
                buf, jnp.asarray(bt[:, :need]))[:, :, :S], pools)

    # 8-token chunks: each chunk's rows and keys are fewer than the
    # forward's, so the fp32 sums may group differently (CPU-dependent,
    # ~1e-6 at layers >= 1 on AVX-512 hosts)
    pools = decoder.init_paged_pools(cfg, 16, page)
    last, pools, _ = _paged_prefill(cfg, params, pools, bt, toks[:, :S], 8)
    assert float(jnp.max(jnp.abs(last[:, -1] - ref_logits[:, 0]))) < 1e-5
    for got, want in zip(jax.tree.leaves(written(pools)),
                         jax.tree.leaves(aux.kv)):
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5

    # One chunk over a table exactly as wide as the prompt's pages runs
    # the forward's shapes, so it must write the forward's K/V bits; the
    # paged decode from there must then match the contiguous decode from
    # the forward's K/V bit-for-bit.
    pools = decoder.init_paged_pools(cfg, 16, page)
    _, pools, _ = _paged_prefill(cfg, params, pools, bt[:, :need],
                                 toks[:, :S], S)
    for got, want in zip(jax.tree.leaves(written(pools)),
                         jax.tree.leaves(aux.kv)):
        assert bool(jnp.array_equal(got, want))

    pos = S
    for t in range(G):
        if -(-(pos + 1) // page) > need:
            bt[0, need] = need
            need += 1
        tok = toks[:, S + t : S + t + 1]
        l_ref, cache = decoder.decode_step(params, cfg, cache, tok,
                                           jnp.int32(pos))
        l_paged, pools, _ = decoder.decode_step_paged(
            params, cfg, pools, jnp.asarray(bt), tok,
            jnp.array([pos], np.int32))
        assert float(jnp.max(jnp.abs(l_ref - l_paged))) == 0.0, t
        pos += 1


def test_paged_decode_local_window(rng):
    """Paged path reproduces the sliding-window ring cache decode."""
    cfg = get_config("gemma3-27b", smoke=True).replace(
        num_layers=4, sliding_window=8
    )
    assert decoder.supports_paged(cfg)
    params = decoder.init_params(cfg, rng)
    S, G, page, W = 16, 10, 4, 8
    toks = jax.random.randint(rng, (1, S + G), 0, cfg.vocab_size)
    ref_logits, _ = decoder.forward(params, cfg, toks, remat=False)
    _, aux = decoder.forward(params, cfg, toks[:, :S], want_kv=True,
                             remat=False, logits_mode="last")
    cache = decoder.init_cache(cfg, 1, S + G)
    cache = decoder.fill_cache_from_prefill(cfg, cache, aux.kv)

    pools = decoder.init_paged_pools(cfg, 16, page)
    bt = np.full((1, W), -1, np.int32)
    need = -(-S // page)
    bt[0, :need] = np.arange(need)
    _paged_out = _paged_prefill(cfg, params, pools, bt, toks[:, :S], 8)
    pools = _paged_out[1]
    pos = S
    for t in range(G):
        if -(-(pos + 1) // page) > need:
            bt[0, need] = need
            need += 1
        tok = toks[:, S + t : S + t + 1]
        l_paged, pools, _ = decoder.decode_step_paged(
            params, cfg, pools, jnp.asarray(bt), tok,
            jnp.array([pos], np.int32))
        err = float(jnp.max(jnp.abs(l_paged[:, 0] - ref_logits[:, S + t])))
        assert err < 2e-4, (t, err)
        pos += 1


def test_chunked_prefill_griffin_stats_equivalence(tiny):
    """Chunk-wise s_sq accumulation == one-shot prefill statistic, and
    the selected expert sets are identical."""
    cfg, params = tiny
    rng = jax.random.PRNGKey(2)
    S, page = 40, 8
    toks = jax.random.randint(rng, (1, S), 0, cfg.vocab_size)

    _, aux = decoder.forward(params, cfg, toks, collect_stats=True,
                             want_kv=False, remat=False, logits_mode="last")
    ref_stats = decoder.prune_stats_tree(aux.stats, cfg)

    pools = decoder.init_paged_pools(cfg, 8, page)
    bt = np.arange(-(-S // page), dtype=np.int32)[None, :]
    _, _, acc = _paged_prefill(cfg, params, pools, bt, toks, 16)

    ref_ssq = jax.tree.leaves(jax.tree.map(
        lambda d: d["s_sq"], ref_stats,
        is_leaf=lambda x: isinstance(x, dict) and "s_sq" in x))
    acc_ssq = jax.tree.leaves(jax.tree.map(
        lambda d: d["s_sq"], acc,
        is_leaf=lambda x: isinstance(x, dict) and "s_sq" in x))
    for r, a in zip(ref_ssq, acc_ssq):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)

    from repro.core import select_tree
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)
    sel_ref = jax.tree.leaves(select_tree(ref_stats, gcfg))
    sel_acc = jax.tree.leaves(select_tree(acc, gcfg))
    for r, a in zip(sel_ref, sel_acc):
        assert np.array_equal(np.asarray(r), np.asarray(a))


# ---------------------------------------------------------------------------
# Scheduler fairness / preemption (engine-free)
# ---------------------------------------------------------------------------

def _mk_sched(num_pages=8, n_slots=2, chunk=16, page=8, maxp=4):
    pcfg = PagedConfig(page_size=page, num_pages=num_pages,
                       max_pages_per_request=maxp)
    return Scheduler(pcfg, n_slots, chunk, metrics=ServingMetrics())


def _drive_prefill(sched):
    """Run plan/finish cycles until the current prefill completes."""
    for _ in range(64):
        plan = sched.plan_step()
        if plan.prefill is None:
            return None
        sched.finish_prefill_chunk(plan.prefill, first_token=0)
        if plan.prefill.is_last:
            return plan.prefill.req
    raise AssertionError("prefill did not complete")


def test_priority_admission_order():
    s = _mk_sched()
    prompt = np.zeros(8, np.int32)
    s.submit(prompt, 4, rid=0, priority=0)
    s.submit(prompt, 4, rid=1, priority=5)
    s.submit(prompt, 4, rid=2, priority=5)
    first = _drive_prefill(s)
    assert first.rid == 1  # highest priority wins; FCFS within priority
    second = _drive_prefill(s)
    assert second.rid == 2


def test_preemption_picks_lowest_priority_latest_arrival():
    # 8-page pool, page_size 8: three 12-token decoders own 2 pages each
    # (room for 4 more tokens before they must grow a 3rd page)
    s = _mk_sched(num_pages=8, n_slots=4)
    prompt = np.zeros(12, np.int32)
    s.submit(prompt, 8, rid=0, priority=1)
    s.submit(prompt, 8, rid=1, priority=0)
    s.submit(prompt, 8, rid=2, priority=0)
    for _ in range(3):
        _drive_prefill(s)
    assert len(s.decoding) == 3 and s.alloc.num_free == 2
    # a new request needs 2 pages for its first chunk + decoders keep
    # growing -> someone must be evicted; victim must be rid=2 (lowest
    # priority, latest arrival)
    s.submit(prompt, 8, rid=3, priority=2)  # takes the 2 free pages
    for _ in range(40):
        plan = s.plan_step()
        if plan.prefill is not None:
            sched_req = plan.prefill.req
            s.finish_prefill_chunk(plan.prefill, first_token=0)
        for r in plan.decode:
            if r.state == DECODING:
                s.finish_decode_token(r, 0)
        if any(r.preemptions for r in s.queue):
            break
    victims = [r for r in s.queue if r.preemptions]
    assert victims and victims[0].rid == 2
    assert victims[0].state == QUEUED and victims[0].prefilled == 0
    assert s.alloc.pages_of(2) == []
    s.alloc.check()
    assert s.metrics.preemptions >= 1


def _drive_all(s, max_steps=500):
    for _ in range(max_steps):
        plan = s.plan_step()
        if plan.prefill is not None:
            s.finish_prefill_chunk(plan.prefill, first_token=0)
        for r in plan.decode:
            if r.state == DECODING:
                s.finish_decode_token(r, 0)
        if not s.has_work:
            return
    raise AssertionError("scheduler did not drain (livelock?)")


def test_no_preemption_livelock_two_big_requests():
    """Two equal-priority requests that cannot coexist in the pool must
    run sequentially, not preempt each other forever: the strictly-worse
    victim rule keeps the earlier arrival's pages pinned."""
    s = _mk_sched(num_pages=6, n_slots=2, chunk=16, page=8, maxp=6)
    prompt = np.zeros(36, np.int32)  # 36 + 8 = 44 tokens -> 6 pages each
    s.submit(prompt, 8, rid=0)
    s.submit(prompt, 8, rid=1)
    _drive_all(s)
    assert len(s.finished) == 2
    assert all(not r.aborted for r in s.finished.values())
    s.alloc.check()


def test_no_priority_inversion_on_admission():
    """A low-priority arrival must not evict a higher-priority decoder;
    it stalls until the decoder finishes and frees its pages."""
    s = _mk_sched(num_pages=4, n_slots=2, chunk=16, page=8, maxp=4)
    s.submit(np.zeros(24, np.int32), 8, rid=0, priority=5)  # grows to 4 pages
    _drive_prefill(s)
    s.submit(np.zeros(16, np.int32), 4, rid=1, priority=0)
    _drive_all(s)
    assert s.finished[0].preemptions == 0 and not s.finished[0].aborted
    assert not s.finished[1].aborted  # served after the decoder drained


def test_duplicate_rid_rejected():
    s = _mk_sched()
    s.submit(np.zeros(8, np.int32), 4, rid=7)
    with pytest.raises(ValueError, match="duplicate"):
        s.submit(np.zeros(8, np.int32), 4, rid=7)


def test_degenerate_requests_rejected():
    s = _mk_sched()
    with pytest.raises(ValueError):
        s.submit(np.zeros(0, np.int32), 4, rid=0)
    with pytest.raises(ValueError):
        s.submit(np.zeros(4, np.int32), 0, rid=1)


def test_stalled_prefill_yields_to_better_arrival():
    """A stalled low-priority prefill must not pin the prefill slot:
    when a strictly-better request arrives, the stalled one is evicted
    and the better one admitted (and may preempt worse decoders)."""
    s = _mk_sched(num_pages=4, n_slots=2, chunk=16, page=8, maxp=4)
    s.submit(np.zeros(24, np.int32), 8, rid=0, priority=5)  # decoder, 3 pages
    _drive_prefill(s)
    s.submit(np.zeros(16, np.int32), 4, rid=1, priority=0)  # will stall
    plan = s.plan_step()
    assert plan.prefill is None  # stalled: cannot evict the better decoder
    assert s.prefilling is not None and s.prefilling.rid == 1
    s.submit(np.zeros(8, np.int32), 2, rid=2, priority=10)
    _drive_all(s)
    assert s.finished[1].preemptions >= 1  # bounced for the better arrival
    order = list(s.finished)  # insertion order == finish order
    assert order.index(2) < order.index(1)
    assert all(not r.aborted for r in s.finished.values())
    s.alloc.check()


def test_oversized_request_rejected():
    s = _mk_sched(maxp=2, page=8)  # capacity 16 tokens
    with pytest.raises(ValueError):
        s.submit(np.zeros(12, np.int32), 8, rid=0)


def test_lone_oversized_for_pool_aborts():
    # fits the block table but not the pool: 4-page pool, needs 4 pages
    # while nothing else can be evicted -> hard abort, no deadlock
    s = _mk_sched(num_pages=2, n_slots=2, page=8, maxp=4)
    s.submit(np.zeros(20, np.int32), 8, rid=0)
    for _ in range(16):
        plan = s.plan_step()
        if plan.prefill is not None:
            s.finish_prefill_chunk(plan.prefill, first_token=0)
        if not s.has_work:
            break
    assert s.finished[0].aborted
    assert s.alloc.num_in_use == 0


# ---------------------------------------------------------------------------
# End-to-end server vs GenerationEngine (greedy parity)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("griffin", [False, True])
def test_server_matches_generate(tiny, griffin):
    from repro.serving.engine import GenerationEngine

    cfg, params = tiny
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False) if griffin else None
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (11, 25, 18)]
    max_new = 6

    eng = GenerationEngine(cfg, params, gcfg=gcfg, max_len=128)
    expected = {
        i: [int(t) for t in np.asarray(eng.generate(jnp.asarray(p)[None],
                                                    max_new))[0]]
        for i, p in enumerate(prompts)
    }

    srv = PagedServer(cfg, params, gcfg=gcfg, page_size=8, num_pages=32,
                      n_slots=2, prefill_chunk=16, max_len=64)
    for i, p in enumerate(prompts):
        srv.submit(p, max_new, rid=i)
    results = srv.drain()
    assert results == expected

    m = srv.metrics.summary()
    assert m["requests_finished"] == len(prompts)
    assert m["generated_tokens"] == len(prompts) * max_new
    assert m["ttft_p50_s"] > 0 and m["tokens_per_sec"] > 0


def test_server_preemption_preserves_outputs(tiny):
    """Recompute-style preemption (with the GRIFFIN expert set frozen at
    first decode) must not change any request's tokens."""
    from repro.serving.engine import GenerationEngine

    cfg, params = tiny
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=24).astype(np.int32)
               for _ in range(3)]
    max_new = 10

    eng = GenerationEngine(cfg, params, gcfg=gcfg, max_len=128)
    expected = {
        i: [int(t) for t in np.asarray(eng.generate(jnp.asarray(p)[None],
                                                    max_new))[0]]
        for i, p in enumerate(prompts)
    }
    # pool deliberately too small for 3 concurrent requests
    srv = PagedServer(cfg, params, gcfg=gcfg, page_size=8, num_pages=10,
                      n_slots=3, prefill_chunk=16, max_len=64)
    for i, p in enumerate(prompts):
        srv.submit(p, max_new, rid=i)
    results = srv.drain()
    assert results == expected
    assert srv.metrics.summary()["preemptions"] >= 1
    srv.sched.alloc.check()


def test_server_mid_decode_preemption_preserves_outputs(tiny):
    """Evicting a request that already compacted and decoded several
    tokens must reproduce the uninterrupted run exactly: the resume
    prefill rebuilds generated-token KV with the request's *selected*
    FF neurons (full weights there would shift every post-resume
    logit)."""
    from repro.serving.engine import GenerationEngine

    cfg, params = tiny
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=20).astype(np.int32)
               for _ in range(2)]
    max_new = 16

    eng = GenerationEngine(cfg, params, gcfg=gcfg, max_len=128)
    expected = {
        i: [int(t) for t in np.asarray(eng.generate(jnp.asarray(p)[None],
                                                    max_new))[0]]
        for i, p in enumerate(prompts)
    }
    # 8-page pool: both requests decode concurrently until the earlier
    # arrival needs its 5th page, which evicts the later one mid-decode
    srv = PagedServer(cfg, params, gcfg=gcfg, page_size=8, num_pages=8,
                      n_slots=2, prefill_chunk=16, max_len=40)
    pruned_resumes = []
    orig_resume = srv._resume_union
    srv._resume_union = lambda r: (pruned_resumes.append(1),
                                   orig_resume(r))[1]
    for i, p in enumerate(prompts):
        srv.submit(p, max_new, rid=i)
    results = srv.drain()
    assert results == expected
    assert srv.metrics.summary()["preemptions"] >= 1
    # the victim really was compacted + mid-decode: its resume re-prefill
    # must have gone through the union FF path
    assert pruned_resumes


def test_resume_prefill_rebuilds_decode_kv_exactly(tiny):
    """The KV a resume prefill rebuilds for generated-token positions
    must match what live decode wrote — which requires the selected FF
    neurons at those positions (this tiny model's greedy tokens collapse,
    so the check must be at logits level, where the full-weight rebuild
    measurably diverges)."""
    from repro.core import griffin as griffin_lib

    cfg, params = tiny
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)
    rng = jax.random.PRNGKey(6)
    S, G, page, W = 16, 4, 8, 4
    prompt = jax.random.randint(rng, (1, S), 0, cfg.vocab_size)

    def fresh_pools():
        return decoder.init_paged_pools(cfg, 8, page)

    bt = np.arange(W, dtype=np.int32)[None]

    # live run: full prefill -> select -> G decode steps over the union
    # buffer of this one request
    logits, pools, stats = decoder.decode_step_paged(
        params, cfg, fresh_pools(), jnp.asarray(bt), prompt,
        jnp.array([0], np.int32), collect_stats=True)
    sel = jax.device_get(griffin_lib.select_per_layer(
        decoder.prune_stats_tree(stats, cfg), gcfg))
    idx, masks, n_live = {}, {}, {}
    for path, per in sel.items():
        F = griffin_lib.ffn_widths(cfg)[path][1]
        rows = [griffin_lib.union_rows([s], F) for s in per]
        idx[path] = np.stack([r[0] for r in rows])
        masks[path] = np.stack([r[1] for r in rows])  # [n, 1, C]
        n_live[path] = np.stack([r[2] for r in rows])
    buf = griffin_lib.gather_union(decoder.extract_ffn_tree(params, cfg),
                                   idx)
    union = {seg: {name: dict(leaf, mask=jnp.asarray(masks[f"{seg}/{name}"]),
                              n_live=jnp.asarray(n_live[f"{seg}/{name}"]))
                   for name, leaf in layers.items()}
             for seg, layers in buf.items()}

    gen = [int(np.argmax(np.asarray(logits)[0, S - 1]))]
    for t in range(G + 1):
        logits, pools, _ = decoder.decode_step_paged(
            params, cfg, pools, jnp.asarray(bt),
            jnp.asarray([[gen[-1]]], np.int32),
            jnp.array([S + t], np.int32), pruned=union)
        gen.append(int(np.argmax(np.asarray(logits)[0, 0])))
    live_logits = np.asarray(logits)  # step consuming gen[G] at pos S+G

    # resume rebuild: prompt with full weights, generated with the
    # request's neurons; then replay the last live step and compare its
    # logits
    def rebuild(use_pruned_for_generated):
        pools_r = fresh_pools()
        _, pools_r, _ = decoder.decode_step_paged(
            params, cfg, pools_r, jnp.asarray(bt), prompt,
            jnp.array([0], np.int32))
        gen_toks = jnp.asarray([gen[:G]], np.int32)  # cached decode inputs
        _, pools_r, _ = decoder.decode_step_paged(
            params, cfg, pools_r, jnp.asarray(bt), gen_toks,
            jnp.array([S], np.int32),
            pruned=union if use_pruned_for_generated else None)
        logits_r, _, _ = decoder.decode_step_paged(
            params, cfg, pools_r, jnp.asarray(bt),
            jnp.asarray([[gen[G]]], np.int32),
            jnp.array([S + G], np.int32), pruned=union)
        return np.asarray(logits_r)

    good = rebuild(use_pruned_for_generated=True)
    np.testing.assert_allclose(good, live_logits, rtol=0, atol=1e-6)
    bad = rebuild(use_pruned_for_generated=False)
    assert float(np.max(np.abs(bad - live_logits))) > 1e-4  # discriminates


def _decode_logits(srv):
    """Record every decode row's logits by (rid, position) as the
    server's jitted decode returns them, and each call's row count."""
    seen, rows = {}, []
    step = srv._decode

    def wrapped(params, pools, bts, toks, pos, mask, pruned):
        logits, pools = step(params, pools, bts, toks, pos, mask, pruned)
        host, pos, mask = np.asarray(logits), np.asarray(pos), np.asarray(mask)
        rows.append(int(mask.sum()))
        for r in srv.sched.decoding:
            if mask[r.slot, 0]:
                seen[(r.rid, int(pos[r.slot]))] = host[r.slot, 0]
        return logits, pools

    srv._decode = wrapped
    return seen, rows


def test_union_decode_matches_each_request_alone(tiny):
    """Four requests with their own selections decode together through
    one union buffer, each with its own mask: every token equals the
    request's solo run and every logit lies within 1e-5 of it."""
    from repro.obs.trace import Tracer

    cfg, params = tiny
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (12, 9, 14, 11)]
    kw = dict(page_size=8, num_pages=48, n_slots=4, prefill_chunk=16,
              max_len=64)

    tr = Tracer()
    srv = PagedServer(cfg, params, gcfg=gcfg, tracer=tr, **kw)
    together, rows = _decode_logits(srv)
    for i, p in enumerate(prompts):
        srv.submit(p, 8, rid=i)
    out = srv.drain()
    k = gcfg.k_of(cfg.d_ff)
    widths = [e["args"]["width"] for e in tr.events
              if e["name"] == "slot_install"]
    assert max(widths) > k  # the selections differ: a real union
    assert max(rows) == 4  # all four shared a tick

    for i, p in enumerate(prompts):
        solo_srv = PagedServer(cfg, params, gcfg=gcfg, **kw)
        alone, _ = _decode_logits(solo_srv)
        solo_srv.submit(p, 8, rid=i)
        assert solo_srv.drain()[i] == out[i], f"rid={i} tokens diverged"
        assert alone and set(alone) <= set(together)
        for key, v in alone.items():
            np.testing.assert_allclose(together[key], v, rtol=0, atol=1e-5)


def test_union_of_one_slot_is_its_selection(tiny):
    """With one live slot the union is the request's own selection: k
    neurons per layer, ceil(k / block) live blocks, a mask of k ones."""
    from repro.core import griffin as griffin_lib

    cfg, params = tiny
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)
    srv = PagedServer(cfg, params, gcfg=gcfg, page_size=8, num_pages=32,
                      n_slots=4, prefill_chunk=16, max_len=64)
    prompt = np.random.default_rng(32).integers(0, cfg.vocab_size, 20)
    srv.submit(prompt.astype(np.int32), 4, rid=0)
    while not srv.sched.decoding:
        srv.step()
    srv.step()
    k = gcfg.k_of(cfg.d_ff)
    u = srv._union
    assert u["width"] == k and u["share"] == k / cfg.d_ff
    slot = srv.sched.decoding[0].slot
    for layers in srv.ffn_union.values():
        for leaf in layers.values():
            n_live = np.asarray(leaf["n_live"])
            assert (n_live == -(-k // griffin_lib.UNION_BLOCK)).all()
            mask = np.asarray(leaf["mask"])
            assert (mask[:, slot].sum(-1) == k).all()
    srv.drain()


# ---------------------------------------------------------------------------
# Pallas paged-gather kernel vs oracle (interpret mode)
# ---------------------------------------------------------------------------

def test_paged_gather_kernel_matches_ref():
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(16, 8, 256)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, 16, size=(4, 6)), jnp.int32)
    out = ops.paged_gather(pool, bt)
    ref = ops.paged_gather_ref(pool, bt)
    assert out.shape == (4, 6, 8, 256)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0


def test_paged_kv_gather_shapes():
    rng = np.random.default_rng(1)
    # [P, KV, page, hd] pool -> token-major [B, n*page, KV, hd] view
    pool = jnp.asarray(rng.normal(size=(9, 2, 4, 16)), jnp.float32)
    bt = jnp.asarray([[0, 3, -1], [8, 2, 1]], jnp.int32)
    out = ops.paged_kv_gather(pool, bt)
    assert out.shape == (2, 12, 2, 16)
    np.testing.assert_array_equal(np.asarray(out[1, 4:8]),
                                  np.asarray(pool[2]).transpose(1, 0, 2))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ops.paged_kv_gather_ref(pool, bt)))


# ---------------------------------------------------------------------------
# Metrics (virtual clock)
# ---------------------------------------------------------------------------

def test_metrics_timeline_virtual_clock():
    t = [0.0]
    m = ServingMetrics(clock=lambda: t[0])
    m.on_submit(0, prompt_tokens=10)
    t[0] = 1.0
    m.on_prefill_chunk(0)
    t[0] = 2.0
    m.on_first_token(0)
    t[0] = 5.0
    for _ in range(3):
        m.on_token(0)
    m.on_finish(0)
    r = m.requests[0]
    assert r.queue_time == 1.0
    assert r.ttft == 2.0
    assert r.tpot == pytest.approx(1.0)  # 3 tokens after first in 3s
    s = m.summary()
    assert s["requests_finished"] == 1
    assert s["ttft_p50_s"] == 2.0
