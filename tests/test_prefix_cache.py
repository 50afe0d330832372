"""Shared-prefix paged-KV reuse tests.

Four layers, cheapest first:

1. **Radix trie unit tests** — node-boundary matching (never mid-edge),
   stat-carrying backtrack, partial-boundary page override, LRU leaf
   eviction, flush; all against a real ``BlockAllocator`` so refcounts
   are exercised, not mocked.
2. **Scheduler-level differential fuzz** (engine-free, injected
   tokens): seeded warm-vs-cold scheduler traces must finish with
   identical outputs and a conserving allocator after *every* plan
   step, while the warm side actually skips prefill chunks.
3. **GRIFFIN stat exactness** — a cached-prefix ``s_sq`` resume must
   equal the cold accumulation bit-for-bit when the resume point is a
   chunk boundary (it always is for mid-prompt nodes), pinned at the
   decoder level and at the server level (identical compacted weights).
4. **Server-level differential fuzz** (trained tiny params, greedy):
   seeded traces with shared Zipf-ish prefixes, preemption pressure and
   ``spec_k`` in {0, 2, 4} on a prefix-warm server vs a cold
   (``prefix_cache=False``) server — token-identical outputs and an
   identical, fully-free allocator after the final flush (the ISSUE's
   acceptance criterion).
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import GriffinConfig
from repro.models import decoder
from repro.serving.metrics import ServingMetrics
from repro.serving.paged import BlockAllocator, PagedConfig
from repro.serving.prefix import PrefixCache
from repro.serving.scheduler import DECODING, Scheduler
from repro.serving.server import PagedServer


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinylm")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def trained():
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.common import trained_tiny

    return trained_tiny(steps=120)


# ---------------------------------------------------------------------------
# Radix trie unit tests
# ---------------------------------------------------------------------------

def _toks(*xs):
    return np.asarray(xs, np.int32)


def test_prefix_match_only_on_node_boundaries():
    a = BlockAllocator(16)
    c = PrefixCache(a, page_size=4)
    donor = a.alloc("r0", 2)  # 8 tokens -> 2 full pages
    c.insert(_toks(*range(8)), donor, s_sq=None)
    # full-prefix extension matches the whole node
    m = c.match(_toks(*range(8), 99), max_len=8)
    assert m is not None and m.length == 8 and m.pages == donor
    # divergence mid-edge: no node boundary to stop at -> miss
    assert c.match(_toks(0, 1, 2, 3, 4, 77, 6, 7, 99), max_len=8) is None
    # max_len cap (at least one prefill token must remain)
    assert c.match(_toks(*range(8)), max_len=7) is None
    a.check()


def test_prefix_chained_nodes_and_partial_boundary_override():
    a = BlockAllocator(16)
    c = PrefixCache(a, page_size=4)
    # donor A: 6 tokens -> pages [p0, p1], p1 partially filled (2/4)
    pa = a.alloc("ra", 2)
    c.insert(_toks(*range(6)), pa, s_sq="sa")
    # donor B extends A to 10 tokens; B COW-forked the boundary page, so
    # its table holds [p0, p1', p2]
    pb = [pa[0]] + a.alloc("rb", 2)
    a.fork([pa[0]], "rb")
    c.insert(_toks(*range(10)), pb, s_sq="sb")
    # matching the long prefix must take B's boundary-page copy, not A's
    m = c.match(_toks(*range(10), 42), max_len=10)
    assert m.length == 10 and m.s_sq == "sb"
    assert m.pages == [pa[0], pb[1], pb[2]]
    # matching only A still sees A's own partial page
    m6 = c.match(_toks(*range(6), 77), max_len=6)
    assert m6.length == 6 and m6.pages == pa and m6.s_sq == "sa"
    a.check()


def test_prefix_stat_backtrack():
    """A stat-needing match must stop at the deepest node that carries
    an s_sq partial — pages past it would drop tokens from selection."""
    a = BlockAllocator(16)
    c = PrefixCache(a, page_size=4)
    p = a.alloc("r0", 3)
    c.insert(_toks(*range(4)), p, s_sq="stat4")
    c.insert(_toks(*range(8)), p, s_sq=None)  # deeper but stat-less
    full = _toks(*range(8), 5)
    assert c.match(full, max_len=8).length == 8
    m = c.match(full, max_len=8, need_stats=True)
    assert m.length == 4 and m.s_sq == "stat4"
    a.check()


def test_prefix_lru_leaf_eviction_and_flush():
    a = BlockAllocator(16)
    c = PrefixCache(a, page_size=4)
    p = a.alloc("r0", 4)
    c.insert(_toks(*range(4)), p, s_sq=None)
    c.insert(_toks(*range(8)), p, s_sq=None)   # child of the first
    c.insert(_toks(9, 9, 9, 9), a.alloc("r1", 1), s_sq=None)
    assert a.num_shared > 0  # trie + donors co-hold the pages
    # while donors still co-hold every page, eviction would free
    # nothing — the cache must refuse to destroy itself for no pages
    assert c.evict_one() == 0
    assert c.num_nodes == 3
    a.free_request("r0"), a.free_request("r1")  # donors finish
    c.match(_toks(9, 9, 9, 9, 1), max_len=4)  # refresh the sibling
    # LRU reclaimable leaf is the depth-8 chain end, not the freshly-
    # touched sibling and not the inner depth-4 node
    assert c.evict_one() > 0
    assert {n.length for n in c.nodes.values()} == {4, 4}
    a.check()
    c.flush()
    assert c.num_nodes == 0 and c.num_pages == 0
    assert a.num_shared == 0 and a.num_in_use == 0  # nothing leaked
    a.check()


def test_prefix_duplicate_insert_upgrades_stats():
    a = BlockAllocator(8)
    c = PrefixCache(a, page_size=4)
    p = a.alloc("r0", 1)
    assert c.insert(_toks(1, 2, 3, 4), p, s_sq=None) is not None
    assert c.insert(_toks(1, 2, 3, 4), p, s_sq="late") is None  # no dup node
    assert c.num_nodes == 1
    assert c.match(_toks(1, 2, 3, 4, 5), max_len=4,
                   need_stats=True).s_sq == "late"
    a.check()


# ---------------------------------------------------------------------------
# Scheduler-level differential fuzz (engine-free)
# ---------------------------------------------------------------------------

def _tok(rid, i):
    return (rid * 31 + i * 7) % 50


def _drive(s: Scheduler, max_steps=3000):
    """Run the scheduler with deterministic injected tokens; check the
    conservation invariant after every plan step."""
    for _ in range(max_steps):
        plan = s.plan_step()
        s.alloc.check()
        if plan.prefill is not None:
            w = plan.prefill
            s.finish_prefill_chunk(w, first_token=_tok(w.req.rid, 0))
        for r in plan.decode:
            if r.state == DECODING:
                s.finish_decode_token(r, _tok(r.rid, len(r.generated)))
        if not s.has_work:
            return
    raise AssertionError("scheduler did not drain")


@pytest.mark.parametrize("seed", range(6))
def test_scheduler_warm_vs_cold_fuzz(seed):
    rng = np.random.default_rng(seed)
    pcfg = PagedConfig(page_size=4, num_pages=24, max_pages_per_request=12)
    shared = [rng.integers(0, 50, size=int(rng.integers(8, 17))).astype(np.int32)
              for _ in range(2)]
    trace = []
    for i in range(10):
        head = shared[int(rng.integers(len(shared)))]
        tail = rng.integers(0, 50, size=int(rng.integers(1, 8))).astype(np.int32)
        trace.append((np.concatenate([head, tail]),
                      int(rng.integers(2, 9)),
                      int(rng.integers(0, 3))))

    outs, chunks = {}, {}
    for mode, pc in (("cold", False), ("warm", True)):
        s = Scheduler(pcfg, n_slots=3, prefill_chunk=8,
                      metrics=ServingMetrics(), prefix_cache=pc)
        for i, (p, mn, prio) in enumerate(trace):
            s.submit(p, mn, rid=i, priority=prio)
        _drive(s)
        outs[mode] = {r: req.generated for r, req in s.finished.items()
                      if not req.aborted}
        chunks[mode] = s.metrics.prefill_chunks
        s.flush_prefix()
        s.alloc.check()
        assert s.alloc.num_in_use == 0  # nothing leaked through sharing
        if pc:
            assert s.metrics.prefix_hits > 0, "trace produced no sharing"
    assert outs["warm"] == outs["cold"]
    assert chunks["warm"] < chunks["cold"]  # reuse actually skipped work


# ---------------------------------------------------------------------------
# GRIFFIN stat exactness: cached s_sq resume == cold accumulation
# ---------------------------------------------------------------------------

def _chunk_stats(cfg, params, toks, chunk, start=0, acc=None):
    """Accumulate paged-prefill s_sq over ``toks[:, start:]`` in
    ``chunk``-token pieces, starting from ``acc``.  With ``start > 0``
    the prefix KV is rebuilt stat-free first — standing in for the
    cached shared pages a warm server forks in (bit-identical bits
    either way: same tokens, same program)."""
    S = toks.shape[1]
    page = 8
    pools = decoder.init_paged_pools(cfg, 16, page)
    bt = np.arange(-(-S // page), dtype=np.int32)[None, :]

    def run(c0, c1, collect):
        nonlocal pools, acc
        for s0 in range(c0, c1, chunk):
            piece = toks[:, s0 : s0 + chunk]
            _, pools, stats = decoder.decode_step_paged(
                params, cfg, pools, jnp.asarray(bt), piece,
                jnp.array([s0], np.int32), collect_stats=collect,
            )
            if collect:
                part = decoder.prune_stats_tree(stats, cfg)
                acc = part if acc is None else jax.tree.map(jnp.add, acc,
                                                            part)

    run(0, start, collect=False)  # prefix KV only; stats come from acc
    run(start, S, collect=True)
    return acc


def test_cached_s_sq_resume_bitexact(tiny):
    """Resuming stat accumulation from a cached chunk-boundary partial
    performs the identical float additions in the identical order as a
    cold prefill — the statistics must be *bit*-equal, not just close
    (so cached-prefix expert selection is sequence-exact)."""
    cfg, params = tiny
    rng = jax.random.PRNGKey(9)
    P, L, chunk = 40, 16, 16  # L: a node boundary (chunk multiple)
    toks = jax.random.randint(rng, (1, P), 0, cfg.vocab_size)

    cold = _chunk_stats(cfg, params, toks, chunk)
    cached = _chunk_stats(cfg, params, toks[:, :L], chunk)  # donor partial
    warm = _chunk_stats(cfg, params, toks, chunk, start=L, acc=cached)

    for c, w in zip(jax.tree.leaves(cold), jax.tree.leaves(warm)):
        np.testing.assert_array_equal(np.asarray(c), np.asarray(w))


def test_server_warm_selection_identical_to_cold(tiny):
    """End-to-end: a prefix-hit request must compact *exactly* the
    weights a cold run selects (bit-equal pruned trees), and emit the
    same tokens."""
    cfg, params = tiny
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)
    rng = np.random.default_rng(21)
    prompt = rng.integers(0, cfg.vocab_size, size=32).astype(np.int32)
    kw = dict(page_size=8, num_pages=48, n_slots=2, prefill_chunk=16,
              max_len=64)

    pruned, outs = {}, {}
    for mode, pc in (("cold", False), ("warm", True)):
        srv = PagedServer(cfg, params, gcfg=gcfg, prefix_cache=pc, **kw)
        srv.submit(prompt, 6, rid=0)  # donor (identical in both modes)
        srv.drain()
        srv.submit(prompt.copy(), 6, rid=1)  # clone
        # the clone's compacted tree, read while it decodes (a finished
        # request releases it)
        while srv.step():
            live = [r for r in srv.sched.decoding
                    if r.rid == 1 and r.compacted]
            if live and mode not in pruned:
                pruned[mode] = live[0].pruned_host
        outs[mode] = srv.drain()
        if pc:
            assert srv.metrics.prefix_hits > 0
            assert srv.metrics.requests[1].prefix_hit_tokens > 0
    assert outs["warm"] == outs["cold"]
    assert jax.tree.leaves(pruned["cold"])
    for c, w in zip(jax.tree.leaves(pruned["cold"]),
                    jax.tree.leaves(pruned["warm"])):
        np.testing.assert_array_equal(np.asarray(c), np.asarray(w))


def test_cow_leaves_donor_pages_intact(tiny):
    """A warm request writing past a shared partial boundary page must
    COW it: re-serving the donor's exact prompt afterwards must still
    reproduce the donor's tokens (the cached page was not scribbled)."""
    cfg, params = tiny
    rng = np.random.default_rng(22)
    prompt = rng.integers(0, cfg.vocab_size, size=20).astype(np.int32)
    diverge = np.concatenate(
        [prompt, rng.integers(0, cfg.vocab_size, size=9).astype(np.int32)])
    srv = PagedServer(cfg, params, gcfg=None, page_size=8, num_pages=48,
                      n_slots=2, prefill_chunk=16, max_len=64)
    srv.submit(prompt, 8, rid=0)
    first = srv.drain()[0]
    srv.submit(diverge, 8, rid=1)  # hits, then COWs the boundary page
    srv.drain()
    assert srv.metrics.cow_copies > 0
    srv.submit(prompt.copy(), 8, rid=2)
    assert srv.drain()[2] == first
    srv.sched.flush_prefix()
    srv.sched.alloc.check()
    assert srv.sched.alloc.num_in_use == 0


# ---------------------------------------------------------------------------
# Server-level differential fuzz: prefix-warm == cold, trained params
# ---------------------------------------------------------------------------

def _mk_trace(cfg, seed, n_req):
    """Zipf-ish shared-prefix trace: most requests reuse prefix 0."""
    rng = np.random.default_rng(seed)
    shared = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
              for n in (16, 24)]
    trace = []
    for i in range(n_req):
        head = shared[0 if rng.random() < 0.7 else 1]
        tail = rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(2, 10))).astype(np.int32)
        trace.append((np.concatenate([head, tail]), int(rng.integers(4, 11))))
    warmup = [(s.copy(), 2) for s in shared]
    return warmup, trace


def _serve(cfg, params, gcfg, warmup, trace, *, spec_k, num_pages,
           prefix_cache):
    srv = PagedServer(cfg, params, gcfg=gcfg, page_size=8,
                      num_pages=num_pages, n_slots=3, prefill_chunk=16,
                      max_len=64, spec_k=spec_k, prefix_cache=prefix_cache)
    for j, (p, mn) in enumerate(warmup):
        srv.submit(p, mn, rid=1000 + j)
    srv.drain()
    for i, (p, mn) in enumerate(trace):
        srv.submit(p, mn, rid=i)
    out = {r: t for r, t in srv.drain().items() if r < 1000}
    return out, srv


@pytest.mark.parametrize("spec_k,seed", [(0, 0), (2, 1), (4, 2)])
def test_differential_fuzz_warm_vs_cold(trained, spec_k, seed):
    """ISSUE acceptance: seeded serving traces (preemption pressure,
    spec_k in {0,2,4}) on a prefix-warm server vs a cold server produce
    token-identical outputs and an identical final allocator state
    (fully free after the flush)."""
    cfg, params = trained
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)
    warmup, trace = _mk_trace(cfg, seed, n_req=6)
    # pool sized so concurrent requests + trie refs force preemption
    num_pages = 11

    cold, srv_c = _serve(cfg, params, gcfg, warmup, trace,
                         spec_k=spec_k, num_pages=num_pages,
                         prefix_cache=False)
    warm, srv_w = _serve(cfg, params, gcfg, warmup, trace,
                         spec_k=spec_k, num_pages=num_pages,
                         prefix_cache=True)
    assert warm == cold
    assert srv_w.metrics.prefix_hits > 0
    assert srv_w.metrics.saved_prefill_tokens > 0
    # the trace is tight enough to exercise the eviction/preemption path
    assert (srv_w.metrics.preemptions + srv_w.metrics.prefix_evictions) > 0
    for srv in (srv_c, srv_w):
        srv.sched.flush_prefix()
        srv.sched.alloc.check()
        assert srv.sched.alloc.num_in_use == 0
    assert sorted(srv_c.sched.alloc._free) == sorted(srv_w.sched.alloc._free)
