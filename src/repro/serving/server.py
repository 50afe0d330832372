"""Paged serving front-end: ``submit`` / ``step`` / ``drain``.

Execution model per ``step()`` (one scheduler tick):

  0. copy-on-write page forks planned by the scheduler are applied to
     the device pools (``decoder.copy_pool_pages``) — shared prefix
     pages are read-only, so a writer first gets a private copy,
  1. at most one prefill chunk of the highest-priority admitted request
     runs through the full model (GRIFFIN stats streamed per chunk) —
     with the prefix cache (``prefix_cache=True``, default) a request
     whose prompt prefix is cached starts at the first divergent token
     with the cached ``s_sq`` partial pre-loaded,
  2. the decode batch advances every DECODING request — by one token in
     a single jitted call over ``n_slots`` padded slots (vanilla), or
     by up to ``spec_k + 1`` tokens per request in a speculative
     draft/verify tick (below).

Prefix reuse is bit-compatible: cached pages hold the very bits the
donor prefill wrote, so warm decode is token-identical to cold decode
(fuzzed in ``tests/test_prefix_cache.py``; mechanism in
``serving/prefix.py`` and DESIGN.md section 9).

Both phases share the per-layer KV page pools; all host state (block
tables, positions, tokens) lives in the scheduler's request objects.
Shapes are static ([1, prefill_chunk], [n_slots, 1], and — with
``spec_k`` — [n_slots, spec_k + 1]); block tables are **narrowed to
the tick's live context** (the widest request's page count, rounded up
to a power of two and capped at ``max_pages_per_request``) before each
jitted call, so the gather-then-attend oracle stops materializing — and
attending over — fully-unallocated tail pages, and the fused kernel's
grid shrinks with it.  The power-of-two rounding bounds the program
count at ``log2(max_pages) + 1`` widths per step type.

Attention backends (``kernel_backend``): ``fused`` runs the Pallas
paged-attention kernel (``kernels/paged_attn.py`` — in-kernel KV
scatter, online softmax over only the pages each request owns),
``gather`` the gather-then-attend oracle, ``auto`` (default) fused on
TPU / gather elsewhere.  Outputs are token-identical either way
(``tests/test_paged_attn_kernel.py``).  The KV pools are **donated**
through every jitted step, so XLA updates pages in place instead of
copying the pool buffers every tick.

GRIFFIN decode (``gcfg``): on a prompt's last chunk the request keeps
only its selected neuron indices per FF layer (one jitted selection,
copied to the host once).  Decode slots hold selections
(``_install_pruned``), and one buffer per FF layer holds the rows of
the union U of the decode batch's selections, neuron-row-major, with
capacity d_ff; each slot has a 0/1 mask over U.  The buffer is
rebuilt (one jitted gather) only when a slot's selection changes, and
the decode FF kernel (``kernels/griffin_ffn.py``) reads only U's
blocks: a slot's output is exactly the sum over its own neurons, and a
tick reads |U| rows per layer instead of one compacted copy per slot.
The union's size is a runtime scalar, so it never adds a program.

Tensor parallelism (``mesh=...``): every jitted step runs shard_mapped
over a 1-D ``model`` mesh axis (``distributed/tp.py``, DESIGN.md
section 11) — KV pools and attention shard along ``kv_heads``
(per-shard pool bytes exactly 1/N), FF weights along the hidden axis
(the union buffer in N parts, each the union of one shard's F-range
with its own live count), block tables / positions / masks
replicated.  All host logic in this file is mesh-agnostic; sharded
serving is token-identical to the single-device path, which stays the
differential oracle (``tests/test_sharded_serving.py``).

Self-speculative decoding (``spec_k > 0``, requires ``gcfg``): the
GRIFFIN selections installed in the decode slots (the union FF) double
as a weight-sharing draft model — the paper's
flocking result says the 50%-FF model is nearly loss-free within a
sequence, so its greedy continuations usually match the dense model's.
One speculative tick per decode batch:

  1. **plan + reserve** — each planned request gets a draft length
     ``k_r = min(k_adaptive, prefill cap, remaining_budget - 1,
     capacity headroom)`` and pre-reserves pages for its ``k_r`` draft
     positions + 1 bonus position, without preemption
     (``Scheduler.reserve_draft``); a request that cannot reserve (pool
     pressure) drafts 0 tokens and its verify row degenerates to a
     vanilla dense decode step.  ``k_adaptive`` is the request's
     learned draft length (``Scheduler.spec_ctl``, a
     ``SpecController`` fed by each round's acceptance; disable with
     ``adaptive_spec=False`` for a fixed ``spec_k``).  While prefill
     work is pending, ``k_r`` is clamped to ``spec_prefill_cap``
     (default 1) so waiting prompts' chunks interleave with short spec
     rounds instead of stalling behind full-``k`` ones — the spec-mode
     TTFT guard.  Only when nobody can draft does the whole tick fall
     back to one-token *dense* decode — with ``spec_k`` the compacted
     weights are only ever the draft, so fallback ticks must not use
     them.
  2. **draft + verify** — ONE fused device program
     (``decoder.draft_verify_paged``): a ``lax.scan`` runs the greedy
     draft iterations *with each slot's GRIFFIN neurons* (argmax
     feedback, draft-KV page writes, per-slot ``k_r`` masking all on
     device), then the same program re-scores the last committed token
     plus each slot's drafts in a ``[n_slots, spec_k + 1]`` dense
     verify pass (rows masked to ``k_r + 1``), overwriting draft KV
     with dense KV at every position it touches.  The whole round
     costs one dispatch + one host sync instead of one of each per
     draft token plus a verify dispatch (``spec_impl="per_token"``
     keeps the old host loop + standalone verify as the differential
     oracle; CI diffs the two).
  4. **commit + rollback** — the greedy acceptance walk
     (``sampling.greedy_verify``) commits accepted drafts plus one
     correction/bonus token through the ordinary scheduler callbacks
     (acceptance also feeds the adaptive controller);
     ``Scheduler.rollback_draft`` returns unused draft pages so
     allocator state is bit-identical to never having drafted.

Greedy speculative output is token-identical to vanilla *dense* greedy
decode (``gcfg=None``) — with ``spec_k`` the compacted weights are an
accelerator, not an approximation.  Acceptance-rate / draft-efficiency
telemetry lands in ``serving/metrics.py``; the wall-clock comparison is
``benchmarks/run.py --only speculative``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import griffin as griffin_lib
from repro.kernels import kv_quant
from repro.models import decoder
from repro.models.layers.attention import resolve_attn_backend
from repro.obs.flocking import FlockingMonitor
from repro.obs.stragglers import StepTimeMonitor
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serving import sampling
from repro.serving.metrics import ServingMetrics
from repro.serving.paged import PagedConfig
from repro.serving.scheduler import (
    DECODING,
    PrefillWork,
    ScheduledRequest,
    Scheduler,
    SpecController,
)


class PagedServer:
    def __init__(
        self,
        cfg,
        params: Dict,
        gcfg: Optional[griffin_lib.GriffinConfig] = None,
        *,
        page_size: int = 16,
        num_pages: int = 96,
        n_slots: int = 4,
        prefill_chunk: int = 32,
        max_len: int = 256,
        spec_k: int = 0,
        spec_impl: str = "fused",
        adaptive_spec: bool = True,
        spec_prefill_cap: int = 1,
        prefix_cache: bool = True,
        kernel_backend: str = "auto",
        kv_dtype: str = "fp32",
        metrics: Optional[ServingMetrics] = None,
        mesh=None,
        tp_axis: str = "model",
        tracer: Optional[Tracer] = None,
        flocking_every: int = 0,
        profile: Optional[griffin_lib.SparsityProfile] = None,
        default_tier: Optional[float] = None,
    ):
        assert decoder.supports_paged(cfg), (
            f"{cfg.name}: paged serving covers attention families only"
        )
        # page-pool byte format (DESIGN.md section 15): fp32 = model
        # dtype (bit-identical legacy pools), bf16 halves pool bytes,
        # int8/fp8 quarter them behind per-page-per-head scale pools
        # that only the attention kernel/oracle ever reads
        self.kv_dtype = kv_quant.resolve_kv_dtype(kv_dtype)
        self.cfg, self.params = cfg, params
        # GRIFFIN selection and the union gather run on single-device
        # arrays; under a mesh ``self.params`` becomes the sharded copy,
        # so keep the original for ``extract_ffn_tree``
        self._host_params = params
        self.gcfg = gcfg if (gcfg is not None and cfg.griffin and cfg.has_ffn) \
            else None
        if self.gcfg is not None and (not cfg.glu or cfg.use_bias):
            raise ValueError(
                f"{cfg.name}: the GRIFFIN union FF kernel covers bias-free "
                f"GLU blocks (activation {cfg.activation!r}, "
                f"use_bias={cfg.use_bias})"
            )
        self.pcfg = PagedConfig(
            page_size=page_size, num_pages=num_pages,
            max_pages_per_request=-(-max_len // page_size),
        )
        self.n_slots = n_slots
        if spec_k and self.gcfg is None:
            raise ValueError(
                "spec_k needs gcfg: the GRIFFIN-compacted weights are the "
                "draft model"
            )
        self.spec_k = spec_k
        if spec_impl not in ("fused", "per_token"):
            raise ValueError(
                f"spec_impl: 'fused' (lax.scan draft loop) or 'per_token' "
                f"(host-loop differential oracle), got {spec_impl!r}"
            )
        self.spec_impl = spec_impl
        self.adaptive_spec = adaptive_spec
        self.spec_prefill_cap = spec_prefill_cap
        self.backend = resolve_attn_backend(kernel_backend)
        self.mesh = mesh
        self.tp = None
        if mesh is not None:
            from repro.distributed.tp import PagedTP

            self.tp = PagedTP(cfg, mesh, axis=tp_axis, backend=self.backend,
                              kv_dtype=self.kv_dtype)
            if self.gcfg is not None and (
                self.gcfg.tp_shards != self.tp.n
                or not self.gcfg.per_shard_topk
            ):
                # balanced shard-local selection with k_ff padded to a
                # multiple of the axis — required for the all-gather-free
                # compacted decode.  To reproduce sharded outputs on one
                # device, pass the same gcfg (tp_shards=N) to the
                # single-device server: the selection math is identical
                # on one host (see repro.core.griffin docstring).
                self.gcfg = self.gcfg.replace(
                    tp_shards=self.tp.n, per_shard_topk=True
                )
        # per-layer profiles + request tiers (DESIGN.md section 16)
        self.profile = profile
        self.default_tier = griffin_lib.resolve_tier(default_tier)
        if (profile is not None or self.default_tier is not None) \
                and self.gcfg is None:
            raise ValueError(
                "sparsity profile/tier needs gcfg: tiers scale the "
                "GRIFFIN per-layer expert budgets"
            )
        self._k_trees: Dict[float, Dict] = {}  # tier -> plan_k_tree
        self._selectors: Dict[Optional[float], Any] = {}  # tier -> jit
        self._ffn_F = griffin_lib.ffn_widths(cfg) if self.gcfg is not None \
            else {}
        # which request each decode slot's selection belongs to
        self._slot_rid: Dict[int, int] = {}
        self.sched = Scheduler(self.pcfg, n_slots, prefill_chunk,
                               metrics=metrics, prefix_cache=prefix_cache)
        self.sched.needs_stats = self.gcfg is not None
        if spec_k and adaptive_spec:
            self.sched.spec_ctl = SpecController(spec_k)
        self.pools = decoder.init_paged_pools(cfg, num_pages, page_size,
                                              self.kv_dtype)
        # the slot table: each decode slot's selection, written only by
        # _install_pruned; the union is built from it
        self.pruned_slots: Optional[List[Dict]] = None
        self._union: Optional[Dict] = None  # see _build_union
        self._gather_union = jax.jit(griffin_lib.gather_union)
        self._next_rid = 0
        self._tick_attn_bytes = 0.0  # modeled KV read bytes, this tick
        # observability (DESIGN.md section 12): the tracer's hooks are
        # no-ops when disabled (NULL_TRACER); the request lifecycle is
        # emitted by ServingMetrics with the clock reads it records, so
        # traces reconcile exactly with summary()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.sched.metrics.tracer = self.tracer
        self.steps_mon = StepTimeMonitor(self.sched.metrics.registry)
        if flocking_every and self.gcfg is None:
            raise ValueError(
                "flocking_every needs gcfg: the telemetry compares the "
                "GRIFFIN expert selection against decode activations"
            )
        self.flocking_every = flocking_every
        self.flocking = FlockingMonitor(self.gcfg,
                                        self.sched.metrics.registry) \
            if flocking_every else None
        self._tick_no = 0
        self._probe = None
        backend = self.backend
        kv_dtype = self.kv_dtype

        if self.tp is not None:
            # shard_map tensor parallelism (distributed/tp.py): pools
            # shard along kv_heads, params along heads/mlp, host-side
            # control (tables, positions, masks) replicated.  The step
            # functions still donate the pools — donation composes with
            # the NamedShardings because every step's out_specs equal
            # its in_specs for the pool tree.
            self._pool_pspecs = self.tp.pool_pspecs(num_pages, page_size)
            self.pools = self.tp.shard_pools(self.pools, num_pages, page_size)
            self.params = self.tp.shard_params(params)
            tp, pool_specs = self.tp, self._pool_pspecs

            def prefill_tp(params, pools, bt, tokens, pos, mask, pruned,
                           collect):
                fn = tp.prefill(pool_specs, collect, pruned)
                return fn(params, pools, bt, tokens, pos, mask, pruned)

            def decode_tp(params, pools, bts, toks, pos, mask, pruned):
                fn = tp.decode(pool_specs, pruned)
                return fn(params, pools, bts, toks, pos, mask, pruned)

            def draft_verify_tp(params, pools, bts, toks, pos, kr, live,
                                pruned, num_steps):
                fn = tp.draft_verify(pool_specs, pruned, num_steps,
                                     self.spec_k)
                return fn(params, pools, bts, toks, pos, kr, live, pruned)

            self._prefill = prefill_tp
            self._decode = decode_tp
            self._draft_verify = draft_verify_tp
            self._verify = tp.verify(pool_specs)
            self._cow_copy = tp.cow(pool_specs)
            if flocking_every:
                self._probe = tp.probe(pool_specs)
            return

        # pools are donated through every step (argnums=1): XLA updates
        # the page buffers in place instead of copying every per-layer
        # pool each tick — the server always reassigns ``self.pools``
        # from the return value, so the stale donated reference is
        # never reused
        def prefill(params, pools, bt, tokens, pos, mask, pruned, collect):
            return decoder.decode_step_paged(
                params, cfg, pools, bt, tokens, pos, write_mask=mask,
                pruned=pruned, collect_stats=collect, backend=backend,
                kv_dtype=kv_dtype,
            )

        self._prefill = jax.jit(prefill, static_argnames=("collect",),
                                donate_argnums=(1,))

        def dec(params, pools, bts, toks, pos, mask, pruned):
            logits, pools, _ = decoder.decode_step_paged(
                params, cfg, pools, bts, toks, pos, write_mask=mask,
                pruned=pruned, backend=backend, kv_dtype=kv_dtype,
            )
            return logits, pools

        self._decode = jax.jit(dec, donate_argnums=(1,))

        # the fused speculative round: draft scan + dense verify in one
        # program, so a round costs a single dispatch and a single host
        # sync.  num_steps is static (pow2-padded max k_r this round),
        # so at most log2(spec_k)+1 distinct programs compile; pools
        # donated like every other step
        spec_k_static = self.spec_k

        def draft_verify(params, pools, bts, toks, pos, kr, live, pruned,
                         num_steps):
            return decoder.draft_verify_paged(
                params, cfg, pools, bts, toks, pos, kr, live,
                pruned=pruned, num_steps=num_steps, spec_k=spec_k_static,
                backend=backend, kv_dtype=kv_dtype,
            )

        self._draft_verify = jax.jit(draft_verify,
                                     static_argnames=("num_steps",),
                                     donate_argnums=(1,))

        def verify(params, pools, bts, toks, pos, mask):
            return decoder.verify_step_paged(
                params, cfg, pools, bts, toks, pos, mask, backend=backend,
                kv_dtype=kv_dtype,
            )

        self._verify = jax.jit(verify, donate_argnums=(1,))

        def cow_copy(pools, src, dst):
            return decoder.copy_pool_pages(cfg, pools, src, dst)

        # pools donated: XLA updates the page buffers in place rather
        # than materializing a full copy of every pool per COW tick
        self._cow_copy = jax.jit(cow_copy, donate_argnums=(0,))

        if flocking_every:

            def probe(params, pools, bts, toks, pos, mask):
                _, _, stats = decoder.decode_step_paged(
                    params, cfg, pools, bts, toks, pos, write_mask=mask,
                    pruned=None, collect_stats=True, backend=backend,
                    kv_dtype=kv_dtype,
                )
                return stats

            # NOT donated: the probe's returned pools (and KV writes)
            # are discarded, so ``self.pools`` stays exactly the state
            # the next real decode step expects
            self._probe = jax.jit(probe)

    # -- API ---------------------------------------------------------------
    @property
    def metrics(self) -> ServingMetrics:
        return self.sched.metrics

    def reset_metrics(self) -> ServingMetrics:
        """Swap in a fresh ``ServingMetrics`` (same clock + tracer) and
        re-home the registry-backed monitors on it.

        For steady-state measurement: drain a warmup trace first (it
        compiles every serving program this workload will hit), call
        this, then run the timed trace — percentiles, counters and the
        throughput window then cover only post-warmup requests instead
        of charging XLA compilation to the first requests' latencies.
        Call only between drains (no live requests — per-request
        timelines would be lost mid-flight).  Serving state is
        deliberately untouched: pages, prefix cache and adaptive
        ``spec_k`` controller state all survive, because resetting
        *measurement* must not change *behavior*."""
        old = self.sched.metrics
        assert not self.sched.has_work, \
            "reset_metrics with live requests would drop their timelines"
        fresh = ServingMetrics(clock=old.clock, tracer=old.tracer)
        self.sched.metrics = fresh
        self.steps_mon = StepTimeMonitor(fresh.registry)
        if self.flocking is not None:
            self.flocking = FlockingMonitor(self.gcfg, fresh.registry)
        return fresh

    def submit(self, prompt: np.ndarray, max_new: int,
               rid: Optional[int] = None, priority: int = 0,
               deadline: Optional[float] = None,
               tier: Optional[float] = None) -> int:
        """``tier`` (one of ``griffin.TIERS``): the fraction of FF
        experts this request keeps — 1.0 decodes dense, lower tiers
        trade perplexity for decode throughput through the per-layer
        profile.  None falls back to the server's ``default_tier``
        (itself None → the legacy global ``gcfg`` budget).  In
        speculative mode tiers do not change outputs: drafts always use
        the global budget and every committed token comes from the
        dense verifier."""
        tier = griffin_lib.resolve_tier(tier)
        if tier is not None and self.gcfg is None:
            raise ValueError(
                f"request tier {tier} needs gcfg: tiers scale the "
                f"GRIFFIN per-layer expert budgets"
            )
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid + 1)
        self.sched.submit(prompt, max_new, rid, priority, deadline=deadline,
                          tier=tier)
        return rid

    def step(self) -> bool:
        """One scheduler tick; returns True while work remains."""
        tr = self.tracer
        metrics = self.sched.metrics
        t0 = metrics.clock()
        self._tick_no += 1
        with tr.span("tick", tick=self._tick_no):
            # host-side planning (no device work) — its own span so a
            # trace separates scheduling cost from device dispatch
            with tr.span("plan"):
                plan = self.sched.plan_step()
            if plan.cow:
                # copy-on-write forks: duplicate shared page bits into
                # the writers' fresh pages before any of this tick's
                # writes
                with tr.span("cow_copy", pairs=len(plan.cow)):
                    self.pools = self._cow_copy(
                        self.pools,
                        jnp.asarray([s for s, _ in plan.cow], jnp.int32),
                        jnp.asarray([d for _, d in plan.cow], jnp.int32),
                    )
            if plan.prefill is not None:
                with tr.span("prefill_chunk", rid=plan.prefill.req.rid,
                             start=plan.prefill.start,
                             tokens=len(plan.prefill.tokens)):
                    self._run_prefill(plan.prefill)
            if plan.decode:
                if self.flocking is not None \
                        and self._tick_no % self.flocking_every == 0:
                    # dense probe *before* the decode/spec step donates
                    # the pools; its writes are discarded
                    with tr.span("flocking_probe", cat="obs",
                                 batch=len(plan.decode)):
                        self._run_flocking_probe(plan.decode)
                ks = self._plan_spec(plan.decode, plan) if self.spec_k \
                    else None
                if ks:
                    with tr.span("spec_round", batch=len(plan.decode),
                                 drafted=sum(ks.values())):
                        self._run_speculative(plan.decode, ks)
                else:
                    with tr.span("decode", batch=len(plan.decode)):
                        self._run_decode(plan.decode)
            metrics.on_step(self.sched.pool_in_use_frac(),
                            len(plan.decode),
                            shared_pages=self.sched.alloc.num_shared,
                            attn_bytes_read=self._tick_attn_bytes)
        self._tick_attn_bytes = 0.0
        if self.flocking is not None:
            for rid in [r for r in self.flocking.live_rids()
                        if r in self.sched.finished]:
                self.flocking.on_finish(rid)
        self.steps_mon.on_tick(metrics.clock() - t0)
        return self.sched.has_work

    def cancel(self, rid: int, reason: str = "cancelled") -> bool:
        """Client-side abort (between ticks): drop the request wherever
        it lives, free its pages, count it as a ``cancelled`` (or
        ``shed``) abort.  Returns False for unknown or already-finished
        rids."""
        ok = self.sched.cancel(rid, reason=reason)
        if ok and self.flocking is not None:
            self.flocking.on_finish(rid)
        return ok

    def drain(self) -> Dict[int, List[int]]:
        """Run until idle; returns generated tokens per finished request."""
        while self.step():
            pass
        self.sync_prefix_gauges()
        return {rid: r.generated for rid, r in self.sched.finished.items()
                if not r.aborted}

    def sync_prefix_gauges(self) -> None:
        """Mirror the prefix trie's shape (``PrefixCache.stats``) onto
        registry gauges so metric snapshots carry cache state."""
        if self.sched.prefix is None:
            return
        for k, v in self.sched.prefix.stats().items():
            self.metrics.registry.gauge(
                f"serving_prefix_{k}",
                help="Prefix-trie shape gauge (see PrefixCache.stats)",
            ).set(v)

    # -- live-context narrowing + modeled attention traffic ----------------
    def _live_width(self, reqs: List[ScheduledRequest]) -> int:
        """Block-table width for this call: the widest request's page
        count, rounded up to a power of two (bounds distinct compiled
        programs at log2(max_pages)+1 per step type), capped at
        ``max_pages_per_request``.  Everything past it is unallocated in
        every row, so narrowing changes no observable value — it only
        stops the oracle from gathering and attending dead tail pages.
        """
        W = self.pcfg.max_pages_per_request
        n = max((len(r.table.pages) for r in reqs), default=1)
        w = 1
        while w < max(n, 1):
            w *= 2
        return min(w, W)

    def _count_attn_bytes(self, pos: List[int], S: int, width: int,
                          rows: int) -> None:
        """Accumulate this call's modeled HBM bytes of KV read by
        attention (the ``attn_bytes_read`` per-tick gauge).  The fused
        kernel streams ``ceil((pos+S)/page)`` owned pages per live
        request; the gather oracle materializes ``width`` pages for
        every row, live or not.  Bytes come from the *pool* itemsize
        (``kv_dtype``), not the model dtype, plus the per-page scale
        bytes quantized pools carry (``kv_quant.page_bytes``)."""
        page = self.pcfg.page_size
        per_page = kv_quant.page_bytes(
            page, self.cfg.num_kv_heads, self.cfg.head_dim,
            self.kv_dtype, self.cfg.dtype,
        )
        if self.backend == "fused":
            pages = sum(-(-(p + S) // page) for p in pos)
        else:
            pages = rows * width
        self._tick_attn_bytes += float(
            self.cfg.num_layers * pages * per_page
        )

    # -- phases ------------------------------------------------------------
    def _run_prefill(self, work: PrefillWork) -> None:
        req, chunk = work.req, self.sched.prefill_chunk
        Lc = len(work.tokens)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :Lc] = work.tokens
        mask = np.zeros((1, chunk), bool)
        mask[0, :Lc] = True
        Wl = self._live_width([req])
        bt = req.table.as_array(Wl)[None]
        pos = np.array([work.start], np.int32)
        self._count_attn_bytes([work.start], Lc, Wl, rows=1)
        collect = work.collect_stats and self.gcfg is not None
        # resume of a compacted request: generated-token positions must
        # rebuild their KV with the same FF neurons that decoded them, or
        # the restored cache (and all post-resume logits) diverge.  In
        # vanilla GRIFFIN mode that is the request's selection; in
        # speculative mode every committed token came from the *dense*
        # verifier, so the rebuild must stay dense too.
        use_pruned = work.use_pruned and not self.spec_k \
            and req.selection is not None  # tier 1.0 rebuilds dense
        pruned = self._resume_union(req) if use_pruned else None
        tr = self.tracer
        with tr.span("prefill_dispatch"):
            logits, self.pools, stats = self._prefill(
                self.params, self.pools, jnp.asarray(bt), jnp.asarray(toks),
                jnp.asarray(pos), jnp.asarray(mask), pruned, collect,
            )
        if collect:
            part = decoder.prune_stats_tree(stats, self.cfg)
            if self.tp is not None:
                # pull the (mesh-replicated, already all-gathered) stats
                # to host single-device arrays: selection/compaction mix
                # them with host params, and eager ops across committed
                # device sets are errors
                part = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)),
                                    part)
            req.s_sq_acc = part if req.s_sq_acc is None else jax.tree.map(
                jnp.add, req.s_sq_acc, part
            )
        first_token = None
        if work.is_last and not req.generated:
            first_token = int(np.argmax(self._fetch(logits)[0, Lc - 1]))
        self.sched.finish_prefill_chunk(work, first_token)
        if work.is_last and req.state == DECODING and self.gcfg is not None \
                and not req.compacted:
            tier = req.tier if req.tier is not None else self.default_tier
            if self.spec_k:
                # drafts always use the global budget: the dense verifier
                # commits every token, so tiering the draft would change
                # speed, never outputs
                tier = None
            if tier == 1.0:
                # dense tier: no selection — every decode of this request
                # runs the unmodified dense program (bit-exact to a
                # no-gcfg server)
                req.selection = None
            else:
                # one jitted selection over the whole statistic tree,
                # copied to the host once; the slot install and the
                # union buffer wait for the decode tick
                with tr.span("compact", rid=req.rid, tier=tier):
                    req.selection = jax.device_get(
                        self._selector(tier)(req.s_sq_acc))
                if self.flocking is not None and tier is None:
                    # frozen selection + the statistic it was made from,
                    # captured before the accumulator drops (telemetry
                    # compares against the global budget, so tiered
                    # requests are not tracked)
                    self.flocking.on_select(
                        req.rid, _selection_tree(req.selection),
                        jax.tree.map(np.asarray, req.s_sq_acc))
            req.compacted = True
            req.s_sq_acc = None

    def _decode_inputs(self, reqs: List[ScheduledRequest]):
        """Padded one-token decode inputs for the batch: each request's
        newest token at its ``cache_len`` position (the same arrays the
        flocking probe replays through the dense model)."""
        B, W = self.n_slots, self._live_width(reqs)
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros((B,), np.int32)
        mask = np.zeros((B, 1), bool)
        bts = np.full((B, W), -1, np.int32)
        for req in reqs:
            s = req.slot
            toks[s, 0] = req.generated[-1]
            pos[s] = req.cache_len
            mask[s, 0] = True
            bts[s] = req.table.as_array(W)
        return toks, pos, mask, bts, W

    def _run_decode(self, reqs: List[ScheduledRequest]) -> None:
        B = self.n_slots
        toks, pos, mask, bts, W = self._decode_inputs(reqs)
        # spec mode: the GRIFFIN neurons are only the *draft* — a
        # vanilla tick (pool-pressure fallback) must decode dense, or its
        # tokens and KV diverge from the dense stream the verifier commits
        use_griffin = self.gcfg is not None and not self.spec_k
        pruned = self._sync_pruned_slots(reqs) if use_griffin else None
        dense_rows = [r for r in reqs if r.selection is None] \
            if use_griffin else list(reqs)
        pruned_rows = [r for r in reqs if r.selection is not None] \
            if use_griffin else []
        if pruned is None:
            groups = [(None, list(reqs))]
        elif not dense_rows:
            groups = [(pruned, list(reqs))]
        else:
            # mixed tick: compacted tiers share the union program;
            # tier-1.0 rows run the unmodified *dense* program in a
            # second dispatch.  Routing dense rows through an all-ones
            # mask over a full union instead is NOT bit-exact (the kernel
            # sums in blocks, ~1e-7 logit wobble), and tier 1.0 promises
            # the dense path bit-exactly.  Each call masks the other
            # group's rows, so KV writes and committed tokens never mix.
            groups = [(pruned, pruned_rows), (None, dense_rows)]
        logits_by_slot = {}
        for pr, group in groups:
            gmask = mask
            if len(group) != len(reqs):
                gmask = np.zeros_like(mask)
                for r in group:
                    gmask[r.slot] = mask[r.slot]
            self._count_attn_bytes([r.cache_len for r in group], 1, W,
                                   rows=B)
            share = {} if pr is None \
                else {"ffn_live_share": self._union["share"]}
            with self.tracer.span("decode_dispatch", **share):
                logits, self.pools = self._decode(
                    self.params, self.pools, jnp.asarray(bts),
                    jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(gmask),
                    pr,
                )
            logits = self._fetch(logits)  # [slots, 1, V]
            for r in group:
                logits_by_slot[r.slot] = logits[r.slot]
        for req in reqs:
            self.sched.finish_decode_token(
                req, int(np.argmax(logits_by_slot[req.slot][0])))

    def _fetch(self, *outs):
        """A step's device outputs as host arrays (one array, or a list
        for several): the wait for the device and the copy to the host,
        each in its own span.  The host needs these values, so the wait
        is the one ``np.asarray`` would make anyway; the copy is queued
        first so that it starts when the step ends, as ``np.asarray``'s
        would, not when the host wakes from the wait."""
        tr = self.tracer
        for o in outs:
            o.copy_to_host_async()
        with tr.span("device_wait"):
            for o in outs:
                o.block_until_ready()
        with tr.span("logits_d2h"):
            host = [np.asarray(o) for o in outs]
        return host[0] if len(host) == 1 else host

    # -- flocking telemetry (obs/flocking.py) ------------------------------
    def _run_flocking_probe(self, reqs: List[ScheduledRequest]) -> None:
        """Dense stats probe over the live decode batch: one un-pruned
        ``decode_step_paged`` with ``collect_stats`` on the *same*
        inputs the coming decode tick uses.  The jit does not donate the
        pools and its outputs (logits, written KV) are discarded, so
        serving state and tokens are untouched — only the per-slot
        ``s_sq`` rows feed the monitor."""
        probed = [r for r in reqs if r.compacted and r.generated]
        if not probed or self._probe is None:
            return
        toks, pos, mask, bts, _ = self._decode_inputs(probed)
        stats = self._probe(
            self.params, self.pools, jnp.asarray(bts),
            jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(mask),
        )
        part = decoder.prune_stats_tree(stats, self.cfg)
        part = jax.tree.map(np.asarray, part)
        results = self.flocking.on_probe(
            {r.rid: r.slot for r in probed}, part)
        for rid, v in results.items():
            self.tracer.ainstant(rid, "flocking", jaccard=v["jaccard"],
                                 angular=v["angular"])

    # -- speculative draft / verify / commit / rollback --------------------
    def _plan_spec(self, reqs: List[ScheduledRequest],
                   plan) -> Optional[Dict[int, int]]:
        """Per-request draft lengths for a speculative tick, pages
        reserved.

        ``k_r = min(k_adaptive, prefill cap, remaining_budget - 1,
        capacity headroom)`` — drafting past a request's ``max_new`` or
        block-table capacity is pure waste, and one constrained request
        must not disable speculation for the whole batch.
        ``k_adaptive`` is the request's learned draft length
        (``SpecController``; ``spec_k`` when the controller is off).

        The *prefill cap*: while a prompt is actively prefilling — a
        chunk in this very plan or a request mid-prefill — a
        full-``k`` round would stretch every tick by ~``k`` sequential
        model steps while that prompt's chunks crawl through one tick
        at a time, which is exactly the spec-mode TTFT inflation the
        benchmark used to show.  Capping ``k_r`` at
        ``spec_prefill_cap`` (default 1) keeps ticks near dense-tick
        latency until the chunks land, so prefill interleaves with
        (rather than waits behind) spec rounds; the SLO/EDF queue
        order decides *which* request prefills, this cap only stops
        drafting from monopolizing the tick.  Merely *queued* requests
        do not engage the cap: a prompt that cannot start prefilling
        yet (no pages / no slot) gains no latency from shorter rounds,
        while the cap would pin every concurrent decode at ``k_r = 1``
        for as long as the backlog lasts — under sustained load that
        is forever, and speculation silently degenerates to
        2-dispatches-per-token.  Greedy token identity is unaffected —
        any ``k_r`` commits the same dense stream.

        A request whose reservation fails (pool pressure) drafts 0
        tokens this round: its verify row then contains only its last
        committed token, which makes that row exactly a vanilla dense
        decode step, already covered by ``plan_step``'s page guarantee.
        Returns ``rid -> k_r``, or None when nobody can draft (the tick
        runs vanilla)."""
        if not all(r.compacted for r in reqs):
            return None
        ctl = self.sched.spec_ctl
        prefill_pending = (plan.prefill is not None
                           or self.sched.prefilling is not None)
        cap = self.spec_prefill_cap if prefill_pending else self.spec_k
        ks: Dict[int, int] = {}
        capped = False
        for r in reqs:
            want = ctl.k_for(r.rid) if ctl is not None else self.spec_k
            k = min(want,
                    r.max_new - len(r.generated) - 1,
                    self.pcfg.max_request_len - r.cache_len - 1)
            k = max(0, k)
            if k > cap:
                k = cap
                capped = True
            if k and not self.sched.reserve_draft(r, k):
                k = 0
            ks[r.rid] = k
        if not any(ks.values()):
            return None
        if capped:
            self.sched.metrics.on_spec_cap()
        return ks

    def _run_speculative(self, reqs: List[ScheduledRequest],
                         ks: Dict[int, int]) -> None:
        """One draft/verify/commit/rollback round for the decode batch
        (per-request draft lengths + pages planned by ``_plan_spec``)."""
        K = self.spec_k
        B, W = self.n_slots, self._live_width(reqs)
        bts = np.full((B, W), -1, np.int32)
        base = {}
        last = {}
        draft: Dict[int, List[int]] = {}
        for req in reqs:
            bts[req.slot] = req.table.as_array(W)
            base[req.rid] = req.cache_len
            last[req.rid] = req.generated[-1]
            draft[req.rid] = []
        bts_j = jnp.asarray(bts)
        num_steps = max(ks.values())
        # in spec mode every request selects at the global budget
        # (tier=None), so every row drafts through the union
        pruned = self._sync_pruned_slots(reqs)

        # modeled attention traffic: at draft iteration ``i`` only the
        # slots still inside their own ``k_r`` are live — masked rows
        # never land in the gauge (counting ``rows=B`` here overstated
        # ``attn_bytes_per_token`` in spec mode), and the verify pass
        # reads one row per planned request, not per slot
        for i in range(num_steps):
            live = [r for r in reqs if i < ks[r.rid]]
            self._count_attn_bytes(
                [base[r.rid] + i for r in live], 1, W, rows=len(live)
            )

        if self.spec_impl == "fused":
            # the whole round — k-step lax.scan draft chain (argmax
            # feedback, per-slot k_r masking, draft-KV page writes,
            # the union FF) AND the [B, K+1] dense verify
            # — runs as ONE device program with ONE host sync
            # (decoder.draft_verify_paged), vs the legacy loop's
            # dispatch + sync per draft token plus a verify dispatch.
            # The scan length pads to the next power of two and the
            # block table to its static maximum width: the program is
            # compiled per (num_steps, width), so both pads bound the
            # distinct-program count at log2(spec_k)+1 total instead of
            # spec_k x log2(max_pages) — without them short benches and
            # adaptive-k churn recompile the scan until it loses to the
            # legacy loop.  Identity is untouched: padded iterations
            # write nothing (k_r mask) and their tokens are sliced off,
            # and dead tail pages sit past every live position, so the
            # causal mask never reads them (see _live_width).
            n_scan = 1 << (num_steps - 1).bit_length()
            Wd = self.pcfg.max_pages_per_request
            btsd = np.full((B, Wd), -1, np.int32)
            toks = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            kr_arr = np.zeros((B,), np.int32)
            live_arr = np.zeros((B,), bool)
            for req in reqs:
                s = req.slot
                btsd[s] = req.table.as_array(Wd)
                toks[s, 0] = last[req.rid]
                pos[s] = base[req.rid]
                kr_arr[s] = ks[req.rid]
                live_arr[s] = True
            with self.tracer.span("draft_verify"):
                dr, vlogits, self.pools = self._draft_verify(
                    self.params, self.pools, jnp.asarray(btsd),
                    jnp.asarray(toks), jnp.asarray(pos),
                    jnp.asarray(kr_arr), jnp.asarray(live_arr),
                    pruned, n_scan,
                )
            # [slots, num_steps], [slots, K+1, V]
            dr, vlogits = self._fetch(dr, vlogits)
            for req in reqs:
                draft[req.rid] = [int(t)
                                  for t in dr[req.slot, : ks[req.rid]]]
        else:
            # legacy per-token host loop — one jitted step, one device
            # sync and one host argmax per draft token.  Kept as the
            # differential oracle for the fused scan: CI runs both modes
            # and fails on any greedy divergence (benchmarks/run.py
            # --only speculative).
            for i in range(num_steps):
                toks = np.zeros((B, 1), np.int32)
                pos = np.zeros((B,), np.int32)
                mask = np.zeros((B, 1), bool)
                for req in reqs:
                    s = req.slot
                    toks[s, 0] = last[req.rid]
                    pos[s] = base[req.rid] + i
                    mask[s, 0] = i < ks[req.rid]
                logits, self.pools = self._decode(
                    self.params, self.pools, bts_j, jnp.asarray(toks),
                    jnp.asarray(pos), jnp.asarray(mask), pruned,
                )
                logits = np.asarray(logits)
                for req in reqs:
                    if i < ks[req.rid]:
                        t = int(np.argmax(logits[req.slot, 0]))
                        draft[req.rid].append(t)
                        last[req.rid] = t

        # verify accounting: one dense pass over last committed token +
        # each request's drafts (static [B, K+1] shape, rows masked to
        # k_r+1).  The fused path already produced the verify logits
        # inside the round's single program; the legacy path dispatches
        # the standalone verify step here.
        self._count_attn_bytes(
            [base[r.rid] + ks[r.rid] for r in reqs], 1, W, rows=len(reqs)
        )
        if self.spec_impl != "fused":
            vtoks = np.zeros((B, K + 1), np.int32)
            vpos = np.zeros((B,), np.int32)
            vmask = np.zeros((B, K + 1), bool)
            for req in reqs:
                s, kr = req.slot, ks[req.rid]
                vtoks[s, 0] = req.generated[-1]
                vtoks[s, 1 : kr + 1] = draft[req.rid]
                vpos[s] = base[req.rid]
                vmask[s, : kr + 1] = True
            with self.tracer.span("verify_step"):
                vlogits, self.pools = self._verify(
                    self.params, self.pools, bts_j, jnp.asarray(vtoks),
                    jnp.asarray(vpos), jnp.asarray(vmask),
                )
            vlogits = self._fetch(vlogits)  # [slots, K+1, V]

        # commit accepted tokens through the vanilla callbacks.  The
        # round telemetry fires *before* the commits: the last commit
        # can finish the request (closing its trace span), and a
        # spec_round instant after the span end would be outside the
        # request's async window.  ``done`` is purely a generated-count
        # check, so the commit count is known up front.
        for req in reqs:
            kr = ks[req.rid]
            committed, n_acc = sampling.greedy_verify(
                vlogits[req.slot, : kr + 1], draft[req.rid]
            )
            n_commit = min(len(committed),
                           req.max_new - len(req.generated))
            if kr:
                self.sched.metrics.on_spec_round(
                    req.rid, drafted=kr, accepted=n_acc, committed=n_commit
                )
                if self.sched.spec_ctl is not None:
                    # the same acceptance numbers the telemetry records
                    # drive next round's draft length for this request
                    self.sched.spec_ctl.observe(req.rid, kr, n_acc)
            for tok in committed:
                if req.done:
                    break
                self.sched.finish_decode_token(req, tok)
        # return unused draft tails in reverse reservation order, so
        # the rollbacks unwind the allocator's LIFO stack exactly (see
        # BlockAllocator.free_pages for the bit-identity scope)
        for req in reversed(reqs):
            self.sched.rollback_draft(req)

    # -- GRIFFIN selections and the union FF --------------------------------
    def _k_tree(self, tier: float) -> Dict:
        """Per-layer expert budgets for a tier (cached — static per
        server: cfg, gcfg and profile never change after init)."""
        if tier not in self._k_trees:
            self._k_trees[tier] = griffin_lib.plan_k_tree(
                self.cfg, self.gcfg, tier=tier, profile=self.profile)
        return self._k_trees[tier]

    def _selector(self, tier: Optional[float]):
        """The jitted selection of every FF layer at a tier (cached: one
        program per tier)."""
        if tier not in self._selectors:
            ks = None if tier is None else self._k_tree(tier)
            self._selectors[tier] = jax.jit(functools.partial(
                griffin_lib.select_per_layer, gcfg=self.gcfg, ks=ks))
        return self._selectors[tier]

    @property
    def ffn_union(self) -> Optional[Dict]:
        """The decode view of the union FF buffers (None before the first
        GRIFFIN decode)."""
        return None if self._union is None else self._union["dec"]

    def _sync_pruned_slots(self, reqs: List[ScheduledRequest]
                           ) -> Optional[Dict]:
        """Bring the union FF up to date for this tick's batch and return
        its decode tree (None when nobody selected — an all-dense-tier
        tick runs the plain dense program)."""
        rows = [r for r in reqs if r.selection is not None]
        if not rows:
            return None
        self._sync_union(rows)
        return self._union["dec"]

    def _resume_union(self, req: ScheduledRequest) -> Dict:
        """The union tree a compacted request's resume prefill runs with:
        the decode batch's slots plus one row for the request, which
        holds no slot while it prefills."""
        self._sync_union([r for r in self.sched.decoding
                          if r.selection is not None], pre=req)
        return self._union["pre"]

    def _sync_union(self, rows: List[ScheduledRequest],
                    pre: Optional[ScheduledRequest] = None) -> None:
        """Install the selections of requests new to their slot, and
        rebuild the union when its rows changed.  A departure needs no
        rebuild: an empty slot's row is masked by the write mask."""
        new = [r for r in rows if self._slot_rid.get(r.slot) != r.rid]
        slots = frozenset(r.slot for r in rows)
        if not new and self._union is not None \
                and slots <= self._union["slots"] \
                and (pre is None or self._union["pre_rid"] == pre.rid):
            return
        # host work; the gather's device ops land in the next wait
        with self.tracer.span("slot_install", installed=len(new)) as sp:
            for r in new:
                self._install_pruned(r.slot, r.selection)
                self._slot_rid[r.slot] = r.rid
            rebuilt, width = self._build_union(slots, pre)
            if sp is not None:
                sp.args.update(rebuilt=rebuilt, width=width)

    def _build_union(self, slots, pre: Optional[ScheduledRequest]):
        """The union of the slot table's selections for ``slots`` (and
        ``pre``'s), per FF layer instance: masks and live counts for
        every decode slot, and the buffer — gathered again only when the
        union itself changed.  Returns (rebuilt, width: the widest
        layer's |U|)."""
        order = sorted(slots)
        sels = [self.pruned_slots[s] for s in order]
        if pre is not None:
            sels.append(pre.selection)
        groups = self.tp.n if self.tp is not None else 1
        idx, dec, pre_mask, n_live, sizes = {}, {}, {}, {}, []
        for path, (n, F) in self._ffn_F.items():
            per = [griffin_lib.union_rows([sel[path][i] for sel in sels], F,
                                          groups) for i in range(n)]
            idx[path] = np.stack([p[0] for p in per])
            masks = np.stack([p[1] for p in per])  # [n, rows, C]
            dec[path] = np.zeros((n, self.n_slots, masks.shape[-1]),
                                 np.float32)
            dec[path][:, order] = masks[:, :len(order)]
            pre_mask[path] = masks[:, len(order):]
            n_live[path] = np.stack([p[2] for p in per])
            sizes += [(p[3], F) for p in per]
        u = self._union
        rebuilt = u is None or any(not np.array_equal(idx[p], u["idx"][p])
                                   for p in idx)
        if rebuilt:
            # free the old buffer before the new one is gathered
            self._union = u = None
            buf = self._gather_union(
                decoder.extract_ffn_tree(self._host_params, self.cfg), idx)
        else:
            buf = u["buf"]
        width = max(sz for sz, _ in sizes)
        self._union = {
            "slots": slots, "pre_rid": None if pre is None else pre.rid,
            "idx": idx, "buf": buf,
            "dec": self._union_tree(buf, dec, n_live),
            "pre": None if pre is None
            else self._union_tree(buf, pre_mask, n_live),
            "share": float(np.mean([sz / F for sz, F in sizes])),
            "width": width,
        }
        return rebuilt, width

    def _union_tree(self, buf: Dict, masks: Dict, n_live: Dict) -> Dict:
        """The step's ``pruned`` tree: each layer's buffers with its masks
        and live counts (``decoder.decode_step_paged``)."""
        tree: Dict[str, Any] = {}
        for path in masks:
            seg, name = path.split("/")
            tree.setdefault(seg, {})[name] = dict(
                buf[seg][name], mask=jnp.asarray(masks[path]),
                n_live=jnp.asarray(n_live[path]))
        if self.tp is not None:
            # the parts of the union shard over the mesh: each device
            # holds its own F-range's union and live counts
            tree = self.tp.shard_pruned(tree)
        return tree

    def _install_pruned(self, slot: int, selection: Dict) -> None:
        """Bind one request's selection to its decode slot.  The first
        call fills every slot with it; the union is built from what this
        writes."""
        if self.pruned_slots is None:
            self.pruned_slots = [selection] * self.n_slots
        else:
            self.pruned_slots[slot] = selection


def _selection_tree(selection: Dict) -> Dict:
    """A per-layer selection (``{path: [idx per instance]}``) in the
    ``griffin.select_tree`` layout: [n, k] for scan leaves, [k] for
    unrolled layers."""
    out: Dict[str, Any] = {}
    for path, sels in selection.items():
        seg, name = path.split("/")
        out.setdefault(seg, {})[name] = np.stack(sels) \
            if name.startswith("pos") else sels[0]
    return out
