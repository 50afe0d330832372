"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and persists each
benchmark's results as ``BENCH_<name>.json`` (the perf trajectory —
see EXPERIMENTS.md section Trajectory).  Quality numbers come from
the framework-trained tiny char-LM (the container is CPU-only; DESIGN.md
section 7 explains the mechanism-scale validation strategy).  Hardware
numbers for the assigned architectures come from the dry-run artifacts
(analytic + XLA roofline terms) — see also EXPERIMENTS.md.

  PYTHONPATH=src python -m benchmarks.run              # everything
  PYTHONPATH=src python -m benchmarks.run --only table2,fig4
  PYTHONPATH=src python -m benchmarks.run --only speculative --smoke
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    bench_tracer,
    drain_results,
    emit,
    eval_sequences,
    record,
    save_trace,
    set_bench_header,
    set_trace_dir,
    timeit,
    trained_tiny,
    write_bench_json,
)
from repro.core import GriffinConfig, evaluate
from repro.core.flocking import flocking_score, pairwise_jaccard, sequence_statistic
from repro.launch.compile_cache import use_compile_cache
from repro.models import decoder


# ---------------------------------------------------------------------------
# Figure 1 / 2: flocking + (lack of) inter-sample similarity
# ---------------------------------------------------------------------------

def bench_flocking() -> None:
    cfg, params = trained_tiny()
    seqs = eval_sequences(cfg, n=6, length=192)
    t0 = time.perf_counter()
    # per-layer activations of sample 0 (want_z)
    _, aux = decoder.forward(params, cfg, seqs[:1], collect_stats=True,
                             want_z=True, remat=False, logits_mode="last")
    st = decoder.prune_stats_tree(aux.stats, cfg)
    z_leaves = jax.tree.leaves(
        jax.tree.map(lambda d: d["z"], st,
                     is_leaf=lambda x: isinstance(x, dict) and "z" in x)
    )
    # z_leaves: stacked [n, 1, S, F] per scan segment
    scores = []
    for leaf in z_leaves:
        zz = leaf.reshape(-1, *leaf.shape[-2:]) if leaf.ndim == 4 else leaf[None]
        for li in range(zz.shape[0]):
            scores.append(flocking_score(zz[li]))
    dt = (time.perf_counter() - t0) * 1e6
    emit("fig1_flocking_intra_seq_jaccard", dt,
         f"mean={np.mean(scores):.3f} min={np.min(scores):.3f} "
         f"max={np.max(scores):.3f} layers={len(scores)}")

    # Figure 2: inter-sample Jaccard of top-50% expert sets (layer 2)
    stats = []
    for i in range(seqs.shape[0]):
        _, aux_i = decoder.forward(params, cfg, seqs[i : i + 1],
                                   collect_stats=True, want_z=True,
                                   remat=False, logits_mode="last")
        st_i = decoder.prune_stats_tree(aux_i.stats, cfg)
        z = jax.tree.leaves(
            jax.tree.map(lambda d: d["z"], st_i,
                         is_leaf=lambda x: isinstance(x, dict) and "z" in x)
        )[0][2, 0]  # layer 2 of the scan stack
        stats.append(sequence_statistic(z))
    inter = pairwise_jaccard(stats, k=cfg.d_ff // 2)
    # intra-sequence: stats from the two halves of the same sequence
    _, auxh = decoder.forward(params, cfg, seqs[:1, :96], collect_stats=True,
                              want_z=True, remat=False, logits_mode="last")
    zh = jax.tree.leaves(jax.tree.map(
        lambda d: d["z"], decoder.prune_stats_tree(auxh.stats, cfg),
        is_leaf=lambda x: isinstance(x, dict) and "z" in x))[0][2, 0]
    _, auxh2 = decoder.forward(params, cfg, seqs[:1, 96:192],
                               collect_stats=True, want_z=True, remat=False,
                               logits_mode="last")
    zh2 = jax.tree.leaves(jax.tree.map(
        lambda d: d["z"], decoder.prune_stats_tree(auxh2.stats, cfg),
        is_leaf=lambda x: isinstance(x, dict) and "z" in x))[0][2, 0]
    from repro.core.flocking import jaccard_topk

    intra = jaccard_topk(sequence_statistic(zh), sequence_statistic(zh2),
                         cfg.d_ff // 2)
    emit("fig2_jaccard_topk50", 0.0,
         f"inter_sample_mean={inter.mean():.3f} intra_sequence={intra:.3f}")


# ---------------------------------------------------------------------------
# Table 1: classification-sim at 50% FF sparsity
# ---------------------------------------------------------------------------

def bench_table1_classification() -> None:
    cfg, params = trained_tiny()
    seqs = eval_sequences(cfg, n=32, length=128)
    for method in ("full", "griffin", "magnitude", "wanda"):
        t0 = time.perf_counter()
        r = evaluate.classification_sim(params, cfg, seqs, method, 0.5)
        dt = (time.perf_counter() - t0) * 1e6
        emit(f"table1_class_{method}", dt,
             f"acc={r['acc']:.3f} agree_full={r['agree_full']:.3f} "
             f"nll={r['nll']:.3f}")


# ---------------------------------------------------------------------------
# Table 2: generation quality (teacher-forced PPL) at 50% FF sparsity
# ---------------------------------------------------------------------------

def bench_table2_generation() -> None:
    cfg, params = trained_tiny()
    seqs = eval_sequences(cfg, n=8, length=192)
    P = 128
    for method in ("full", "griffin", "magnitude", "wanda"):
        t0 = time.perf_counter()
        ppl = evaluate.generation_ppl(params, cfg, seqs, P, method, 0.5)
        dt = (time.perf_counter() - t0) * 1e6
        emit(f"table2_gen_{method}", dt, f"ppl={ppl:.4f}")


# ---------------------------------------------------------------------------
# Figure 4: performance vs FF sparsity
# ---------------------------------------------------------------------------

def bench_fig4_sparsity() -> None:
    cfg, params = trained_tiny()
    seqs = eval_sequences(cfg, n=6, length=192)
    P = 128
    base = evaluate.generation_ppl(params, cfg, seqs, P, "full")
    for sp in (0.0, 0.25, 0.5, 0.75, 0.9):
        t0 = time.perf_counter()
        ppl = evaluate.generation_ppl(params, cfg, seqs, P, "griffin", sp)
        dt = (time.perf_counter() - t0) * 1e6
        emit(f"fig4_sparsity_{sp}", dt,
             f"ppl={ppl:.4f} rel={base / ppl:.4f}")


# ---------------------------------------------------------------------------
# Figure 5: prompt length vs generation length
# ---------------------------------------------------------------------------

def bench_fig5_prompt_gen() -> None:
    cfg, params = trained_tiny()
    for P in (32, 64, 128):
        for G in (32, 64, 128):
            seqs = eval_sequences(cfg, n=4, length=P + G)
            full = evaluate.generation_ppl(params, cfg, seqs, P, "full")
            t0 = time.perf_counter()
            g = evaluate.generation_ppl(params, cfg, seqs, P, "griffin", 0.5)
            dt = (time.perf_counter() - t0) * 1e6
            emit(f"fig5_P{P}_G{G}", dt,
                 f"ppl_full={full:.4f} ppl_griffin={g:.4f} "
                 f"delta={g - full:+.4f}")


# ---------------------------------------------------------------------------
# Table 4: sharing selected neurons across samples (batched eq. 7)
# ---------------------------------------------------------------------------

def bench_table4_batching() -> None:
    cfg, params = trained_tiny()
    all_seqs = eval_sequences(cfg, n=16, length=192)
    P = 128

    # GRIFFIN with batch sizes 1 / 4 / 16 (eq. 7 aggregation per batch)
    for bs in (1, 4, 16):
        t0 = time.perf_counter()
        ppls = []
        for i in range(0, 16, bs):
            ppls.append(evaluate.generation_ppl(
                params, cfg, all_seqs[i : i + bs], P, "griffin", 0.5))
        dt = (time.perf_counter() - t0) * 1e6
        emit(f"table4_griffin_b{bs}", dt, f"ppl={np.mean(ppls):.4f}")

    # "Global": one expert set from the whole dataset's aggregated stats
    _, aux = evaluate.prompt_stats(params, cfg, all_seqs[:, :P])
    pruned, _ = evaluate.build_pruned("griffin", params, cfg, aux.stats, 0.5)
    B, S = all_seqs.shape
    cache = decoder.init_cache(cfg, B, S)
    cache = decoder.fill_cache_from_prefill(cfg, cache, aux.kv)
    dec = jax.jit(lambda c, t, pos: decoder.decode_step(
        params, cfg, c, t, pos, pruned))
    nll, cnt = 0.0, 0
    t0 = time.perf_counter()
    for t in range(P - 1, S - 1):
        logits, cache = dec(cache, all_seqs[:, t : t + 1], jnp.int32(t))
        logp = jax.nn.log_softmax(logits[:, 0].astype(jnp.float32), -1)
        nll += float(-jnp.sum(jnp.take_along_axis(
            logp, all_seqs[:, t + 1][:, None], 1)))
        cnt += B
    dt = (time.perf_counter() - t0) * 1e6
    emit("table4_global_static", dt, f"ppl={np.exp(nll / cnt):.4f}")


# ---------------------------------------------------------------------------
# Table 5 (Appendix B): selection method ablation
# ---------------------------------------------------------------------------

def bench_table5_selection() -> None:
    cfg, params = trained_tiny()
    seqs = eval_sequences(cfg, n=6, length=192)
    P = 128
    rng = jax.random.PRNGKey(0)
    for method in ("griffin", "sampling", "topk_sampling", "blocks"):
        t0 = time.perf_counter()
        ppl = evaluate.generation_ppl(params, cfg, seqs, P, method, 0.5, rng=rng)
        dt = (time.perf_counter() - t0) * 1e6
        emit(f"table5_select_{method}", dt, f"ppl={ppl:.4f}")


# ---------------------------------------------------------------------------
# Table 3: generation latency (measured tiny + derived v5e)
# ---------------------------------------------------------------------------

def bench_table3_latency() -> None:
    cfg, params = trained_tiny()
    B, P, C = 1, 128, 256
    seqs = eval_sequences(cfg, n=B, length=P)
    _, aux = evaluate.prompt_stats(params, cfg, seqs)
    cache = decoder.init_cache(cfg, B, C)
    cache = decoder.fill_cache_from_prefill(cfg, cache, aux.kv)
    tok = seqs[:, -1:]

    variants = {
        "full": (None, 0.0),
        "griffin50": ("griffin", 0.5),
        "griffin75": ("griffin", 0.75),
        "magnitude50": ("magnitude", 0.5),
    }
    for name, (method, sp) in variants.items():
        pruned = None
        if method:
            pruned, _ = evaluate.build_pruned(method, params, cfg, aux.stats, sp)
        dec = jax.jit(lambda c, t, pr=pruned: decoder.decode_step(
            params, cfg, c, t, jnp.int32(P), pr))
        us = timeit(dec, cache, tok, warmup=3, iters=10)
        emit(f"table3_decode_{name}", us, f"B={B} ctx={P} (CPU wall-time)")

    # derived v5e decode-step latency for the big archs (analytic roofline)
    from repro.analysis import analytic
    from repro.analysis.roofline import HBM_BW, PEAK_FLOPS
    from repro.configs.registry import get_config
    from repro.configs.shapes import SHAPES

    for arch in ("yi-9b", "gemma3-27b", "command-r-plus-104b"):
        acfg = get_config(arch)
        shape = SHAPES["decode_32k"]
        full = analytic.cell_cost(acfg, shape, griffin_sparsity=0.0)
        grif = analytic.cell_cost(acfg, shape, griffin_sparsity=0.5)
        chips = 256
        t_full = max(full.flops / chips / PEAK_FLOPS,
                     full.hbm_bytes / chips / HBM_BW)
        t_grif = max(grif.flops / chips / PEAK_FLOPS,
                     grif.hbm_bytes / chips / HBM_BW)
        emit(f"table3_v5e_derived_{arch}", t_full * 1e6,
             f"griffin_us={t_grif * 1e6:.1f} speedup={t_full / t_grif:.3f}x "
             f"(per decode step, 256 chips)")


# ---------------------------------------------------------------------------
# Kernels: wall time (interpret mode) + correctness confirmation
# ---------------------------------------------------------------------------

def bench_kernels() -> None:
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    B, D, F = 4, 256, 2048
    x = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)
    ws = [jnp.asarray(rng.normal(size=(1, F, D)) * 0.05, jnp.float32)
          for _ in range(3)]
    mask = jnp.ones((B, F), jnp.float32)
    n_live = 8  # the buffer's first 8 blocks of 128 neurons
    us = timeit(lambda: ops.griffin_ffn_decode(x, *ws, mask, 0, n_live),
                iters=3)
    err = float(jnp.max(jnp.abs(
        ops.griffin_ffn_decode(x, *ws, mask, 0, n_live)
        - ops.griffin_ffn_ref(x, *ws, mask, 0, n_live, 128)
    )))
    emit("kernel_griffin_ffn_interpret", us, f"max_err_vs_ref={err:.2e}")

    z = jnp.asarray(rng.normal(size=(512, F)), jnp.float32)
    us = timeit(lambda: ops.griffin_stat(z), iters=3)
    err = float(jnp.max(jnp.abs(ops.griffin_stat(z) - ops.expert_stat_ref(z))))
    emit("kernel_expert_stat_interpret", us, f"max_err_vs_ref={err:.2e}")


# ---------------------------------------------------------------------------
# Decode attention: fused paged-attention kernel vs gather-then-attend
# ---------------------------------------------------------------------------

def bench_decode_attn(smoke: bool = False, kv_dtype: str = "fp32") -> None:
    """Fused paged-attention decode kernel vs the gather-then-attend
    oracle (the serving decode hot path; kernels/paged_attn.py).

    Two measurements per block-table width (= ``max_len / page``):
    median wall time of one jitted ``decode_step_paged`` tick for each
    backend (CPU: the oracle runs as XLA gather + dense softmax, the
    fused kernel in Pallas *interpret* mode — so the wall-clock
    comparison here is NOT the TPU story; interpret mode pays a large
    per-grid-step python cost), and the modeled HBM bytes/token each
    path reads (the hardware-independent signal): the oracle reads the
    full ``B * W * page`` KV positions per tick regardless of how much
    context is live, the fused kernel only ``ceil(ctx/page)`` owned
    pages per request — flat in ``max_len``, linear in live context.
    The derived v5e section scales the same formulas to a big assigned
    arch (yi-9b) with ``analysis/roofline.py`` HBM bandwidth, which is
    where the bytes gap becomes decode-step time.

    ``kv_dtype`` selects the KV-pool storage dtype for the measured
    ticks (kernels/kv_quant.py).  Two quantization sections ride along
    regardless of the measured dtype:

    * ``kv_dtype_sweep`` — modeled attention bytes/token and pool
      bytes/request for every supported pool dtype, plus how many
      concurrent requests a fixed pool-byte budget holds (int8 must
      clear >= 1.9x fp32 on both, asserted).
    * ``pool_capacity`` — the same capacity math scaled to yi-9b at
      serving context (where int8 pages turn directly into batch).

    When ``kv_dtype`` is quantized the run also measures the kernel's
    max context error against the *fp32* oracle on unit-Gaussian KV
    (asserted <= ``kv_quant.ERROR_BUDGET``) and serves the trained tiny
    model end-to-end for a greedy token-match rate + teacher-forced
    perplexity delta vs an fp32-pool server (asserted >=
    ``kv_quant.TOKEN_MATCH_FLOOR``) — the CI smoke gate for the
    documented error budget.
    """
    from repro.analysis.roofline import HBM_BW
    from repro.configs.registry import get_config
    from repro.kernels import kv_quant

    kvd = kv_quant.resolve_kv_dtype(kv_dtype)
    cfg = get_config("tinylm")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    B, page, ctx = 4, 16, 40
    widths = (4, 8) if smoke else (4, 8, 16)
    iters = 2 if smoke else 3

    KV, hd = cfg.num_kv_heads, cfg.head_dim

    def kv_bytes_per_tok(c, KV, hd, n_layers, dtype, model_dtype):
        # pages are read whole: data bytes at the pool itemsize plus
        # the per-page scale rows for quantized dtypes
        pages = -(-c // page)
        return pages * kv_quant.page_bytes(page, KV, hd, dtype,
                                           model_dtype) * n_layers

    set_bench_header(kv_dtype=kvd)
    need = -(-(ctx + 1) // page)
    tiny = {}
    for W in widths:
        pools = decoder.init_paged_pools(cfg, B * W + 2, page, kvd)
        bts = np.full((B, W), -1, np.int32)
        for b in range(B):
            bts[b, :need] = np.arange(b * need, (b + 1) * need)
        toks = jnp.asarray(np.full((B, 1), 7, np.int32))
        pos = jnp.asarray(np.full((B,), ctx, np.int32))
        mask = jnp.ones((B, 1), bool)
        row = {}
        for backend in ("gather", "fused"):
            step = jax.jit(lambda pr, po, bt, tk, ps, mk, _b=backend:
                           decoder.decode_step_paged(
                               pr, cfg, po, bt, tk, ps, write_mask=mk,
                               backend=_b, kv_dtype=kvd))
            us = timeit(lambda: step(params, pools, jnp.asarray(bts),
                                     toks, pos, mask),
                        warmup=1, iters=iters)
            pages_read = W if backend == "gather" else need
            bpt = kv_bytes_per_tok(pages_read * page, KV, hd,
                                   cfg.num_layers, kvd, cfg.dtype)
            row[backend] = {"us_per_call": us, "model_bytes_per_token": bpt}
            emit(f"decode_attn_{backend}_W{W}", us,
                 f"B={B} ctx={ctx} max_len={W * page} kv_dtype={kvd} "
                 f"bytes_per_token={bpt:.0f} (interpret-mode wall time)")
        tiny[f"W{W}"] = row

    # modeled bytes/token sweep: fused is flat in max_len, the oracle
    # scales with it; fused scales with the *live* context instead
    sweep = {}
    for max_len in (256, 1024, 4096):
        c_pages = -(-(ctx + 1) // page) * page
        sweep[str(max_len)] = {
            "oracle": kv_bytes_per_tok(max_len, KV, hd, cfg.num_layers,
                                       kvd, cfg.dtype),
            "fused": kv_bytes_per_tok(c_pages, KV, hd, cfg.num_layers,
                                      kvd, cfg.dtype),
        }
    fused_vals = {v["fused"] for v in sweep.values()}
    flat = len(fused_vals) == 1
    emit("decode_attn_bytes_flat_in_max_len", 0.0,
         f"fused={sorted(fused_vals)} oracle="
         f"{[v['oracle'] for v in sweep.values()]} flat={flat}")

    # -- kv_dtype sweep: pool bytes vs capacity at a fixed budget ----------
    # pool bytes one tinylm request pins (max_len tokens of pages) and
    # how many requests a fixed fp32-sized budget holds per dtype
    max_len_req = 128
    req_pages = max_len_req // page
    dtypes = [d for d in kv_quant.KV_DTYPES
              if d != "fp8" or hasattr(jnp, "float8_e4m3fn")]
    per_req = {
        d: cfg.num_layers * req_pages * kv_quant.page_bytes(
            page, KV, hd, d, cfg.dtype)
        for d in dtypes
    }
    pool_budget = 32 * per_req["fp32"]  # 32 fp32 requests' worth of pool
    kv_sweep = {}
    for d in dtypes:
        bpt = kv_bytes_per_tok(ctx + 1, KV, hd, cfg.num_layers, d,
                               cfg.dtype)
        cap = pool_budget // per_req[d]
        kv_sweep[d] = {
            "attn_bytes_per_token": bpt,
            "pool_bytes_per_request": per_req[d],
            "max_concurrent_at_budget": int(cap),
            "bytes_per_token_vs_fp32": kv_sweep.get("fp32", {}).get(
                "attn_bytes_per_token", bpt) / bpt,
            "capacity_vs_fp32": cap / max(
                kv_sweep.get("fp32", {}).get(
                    "max_concurrent_at_budget", cap), 1),
        }
        emit(f"decode_attn_kv_{d}", 0.0,
             f"bytes_per_token={bpt} pool_bytes_per_request={per_req[d]} "
             f"max_concurrent@{pool_budget}B={int(cap)} "
             f"({kv_sweep[d]['bytes_per_token_vs_fp32']:.2f}x fewer "
             f"bytes/token vs fp32)")
    assert kv_sweep["int8"]["bytes_per_token_vs_fp32"] >= 1.9, kv_sweep
    assert kv_sweep["int8"]["capacity_vs_fp32"] >= 1.9, kv_sweep

    # -- pool_capacity: the same math at yi-9b serving scale ---------------
    acfg = get_config("yi-9b")
    aKV, ahd, alayers = acfg.num_kv_heads, acfg.head_dim, acfg.num_layers
    a_max_len, a_budget = 32768, 8 << 30  # 8 GiB of HBM left for KV
    a_pages = a_max_len // page
    pool_capacity = {"budget_bytes": a_budget, "max_len": a_max_len,
                     "arch": "yi-9b", "per_dtype": {}}
    for d in dtypes:
        pr = alayers * a_pages * kv_quant.page_bytes(page, aKV, ahd, d,
                                                     "bfloat16")
        pool_capacity["per_dtype"][d] = {
            "pool_bytes_per_request": pr,
            "max_concurrent_requests": int(a_budget // pr),
        }
    cap8 = pool_capacity["per_dtype"]["int8"]["max_concurrent_requests"]
    cap32 = pool_capacity["per_dtype"]["fp32"]["max_concurrent_requests"]
    emit("decode_attn_pool_capacity_yi9b", 0.0,
         f"budget=8GiB max_len={a_max_len} fp32={cap32}req "
         f"int8={cap8}req ({cap8 / max(cap32, 1):.1f}x)")

    # -- quantized-dtype quality gates (CI smoke for the error budget) -----
    quality = None
    if kv_quant.is_quantized(kvd):
        err = _kernel_error_vs_fp32_oracle(cfg, kvd)
        match, ppl_fp32, ppl_q = _trained_tiny_kv_quality(kvd, smoke)
        quality = {
            "kernel_max_ctx_error_vs_fp32": err,
            "error_budget": kv_quant.ERROR_BUDGET[kvd],
            "token_match_rate": match,
            "token_match_floor": kv_quant.TOKEN_MATCH_FLOOR[kvd],
            "ppl_fp32": ppl_fp32, "ppl_quantized": ppl_q,
            "ppl_delta": ppl_q - ppl_fp32,
        }
        emit(f"decode_attn_quality_{kvd}", 0.0,
             f"max_ctx_err={err:.4f} (budget "
             f"{kv_quant.ERROR_BUDGET[kvd]}) token_match={match:.3f} "
             f"(floor {kv_quant.TOKEN_MATCH_FLOOR[kvd]}) "
             f"ppl_delta={ppl_q - ppl_fp32:+.4f}")

    # derived v5e decode-step attention-read time for a big arch
    v5e = {}
    for live_ctx, max_len in ((2048, 32768), (8192, 32768)):
        ob = kv_bytes_per_tok(max_len, aKV, ahd, alayers, kvd, "bfloat16")
        fb = kv_bytes_per_tok(live_ctx, aKV, ahd, alayers, kvd, "bfloat16")
        v5e[f"ctx{live_ctx}_max{max_len}"] = {
            "oracle_bytes_per_token": ob,
            "fused_bytes_per_token": fb,
            "oracle_attn_read_us": ob / HBM_BW * 1e6,
            "fused_attn_read_us": fb / HBM_BW * 1e6,
        }
        emit(f"decode_attn_v5e_yi9b_ctx{live_ctx}", fb / HBM_BW * 1e6,
             f"oracle_us={ob / HBM_BW * 1e6:.1f} "
             f"speedup={ob / fb:.1f}x (per decode step, attn KV reads, "
             f"max_len={max_len}, kv_dtype={kvd})")
    record("smoke", bool(smoke))
    record("kv_dtype", kvd)
    record("tiny", tiny)
    record("bytes_per_token_by_max_len", sweep)
    record("fused_flat_in_max_len", bool(flat))
    record("kv_dtype_sweep", kv_sweep)
    record("pool_capacity", pool_capacity)
    if quality is not None:
        record("quantized_quality", quality)
    record("v5e_derived", v5e)
    assert flat, "fused bytes/token must not depend on max_len"
    if quality is not None:
        assert quality["kernel_max_ctx_error_vs_fp32"] <= \
            quality["error_budget"], quality
        assert quality["token_match_rate"] >= \
            quality["token_match_floor"], quality


def _kernel_error_vs_fp32_oracle(cfg, kvd: str) -> float:
    """Max |ctx| error of the fused kernel on ``kvd`` pools vs the fp32
    oracle, over a few unit-Gaussian decode ticks (the documented
    ERROR_BUDGET setting)."""
    from repro.kernels import kv_quant, ops

    rng = np.random.default_rng(11)
    B, S, page, P, W = 2, 1, 16, 8, 3
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    H = cfg.num_heads
    bt = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    wm = jnp.ones((B, S), bool)
    gp = jnp.arange(P + 1).repeat(page)
    off = jnp.tile(jnp.arange(page), P + 1)
    worst = 0.0
    for trial in range(3):
        q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
        pkf = jnp.asarray(rng.normal(size=(P + 1, KV, page, hd)),
                          jnp.float32)
        pvf = jnp.asarray(rng.normal(size=(P + 1, KV, page, hd)),
                          jnp.float32)
        pos = jnp.asarray(rng.integers(page, page * 2, size=(B,)),
                          jnp.int32)
        ctx_f = ops.paged_attn_ref(q, kn, vn, pkf, pvf, bt, pos, wm)[0]
        z = jnp.zeros((P + 1, KV, page, hd),
                      kv_quant.pool_jnp_dtype(kvd, cfg.dtype))
        s0 = jnp.zeros((P + 1, KV, 1, 1), jnp.float32)
        # page-major rows [(P+1)*page, KV, hd] in (page, offset) order
        pkq, sk = kv_quant.quantize_scatter_ref(
            z, s0, gp, off, pkf.transpose(0, 2, 1, 3).reshape(-1, KV, hd),
            kvd)
        pvq, sv = kv_quant.quantize_scatter_ref(
            z, s0, gp, off, pvf.transpose(0, 2, 1, 3).reshape(-1, KV, hd),
            kvd)
        ctx_q = ops.paged_attention(q, kn, vn, pkq, pvq, bt, pos, wm,
                                    scale_k=sk, scale_v=sv,
                                    kv_dtype=kvd)[0]
        worst = max(worst, float(jnp.max(jnp.abs(ctx_q - ctx_f))))
    return worst


def _trained_tiny_kv_quality(kvd: str, smoke: bool):
    """Serve the trained tiny model with ``kvd`` pools vs fp32 pools:
    greedy token-match rate across the drained requests, plus the
    teacher-forced perplexity of each server's generations under the
    fp32 model (quality delta attributable to quantized KV)."""
    from repro.core import evaluate
    from repro.data.pipeline import SyntheticCorpus
    from repro.serving.server import PagedServer

    cfg, params = trained_tiny(steps=120 if smoke else 500)
    corpus = SyntheticCorpus(vocab=cfg.vocab_size, seed=0)
    n_req = 4 if smoke else 8
    max_new = 12 if smoke else 24
    rng = np.random.default_rng(31)
    prompts = [corpus.sample(int(rng.integers(24, 64)), seed=9100 + i)
               for i in range(n_req)]
    outs = {}
    for mode in ("fp32", kvd):
        srv = PagedServer(cfg, params, gcfg=None, page_size=16,
                          num_pages=96, n_slots=4, prefill_chunk=32,
                          max_len=128, kv_dtype=mode)
        for i, p in enumerate(prompts):
            srv.submit(p, max_new=max_new, rid=i)
        outs[mode] = srv.drain()
    matched = total = 0
    ppl = {}
    for mode in ("fp32", kvd):
        nll = cnt = 0
        for i in range(n_req):
            seq = np.concatenate([prompts[i], np.asarray(outs[mode][i])])
            P = len(prompts[i])
            ppl_i = evaluate.generation_ppl(
                params, cfg, jnp.asarray(seq[None]), P, "full")
            nll += np.log(ppl_i) * (len(seq) - P)
            cnt += len(seq) - P
        ppl[mode] = float(np.exp(nll / max(cnt, 1)))
    for i in range(n_req):
        a, b = outs["fp32"][i], outs[kvd][i]
        matched += sum(x == y for x, y in zip(a, b))
        total += max(len(a), len(b))
    return matched / max(total, 1), ppl["fp32"], ppl[kvd]


# ---------------------------------------------------------------------------
# Serving: paged-KV stack under a Poisson arrival trace (GRIFFIN on/off)
# ---------------------------------------------------------------------------

def bench_serving() -> None:
    """16-request Poisson trace through the paged serving stack.

    Requests arrive by wall clock (exponential inter-arrival times);
    the server steps continuously — chunked prefill interleaved with the
    decode batch — and the per-request telemetry yields tokens/sec and
    p50/p95 TTFT, with per-request GRIFFIN on vs. off.

    CPU caveat: the GRIFFIN decode FFN runs the union kernel
    (kernels/griffin_ffn.py) in Pallas interpret mode here, slower than
    one shared dense matmul despite fewer FLOPs — the GRIFFIN win is a
    TPU HBM-bandwidth effect (a tick reads the union's neuron rows
    instead of all F; see table3's derived v5e numbers).
    """
    from repro.data.pipeline import SyntheticCorpus
    from repro.serving.server import PagedServer

    cfg, params = trained_tiny()
    corpus = SyntheticCorpus(vocab=cfg.vocab_size, seed=0)
    n_req, mean_gap_s = 16, 0.05
    rng = np.random.default_rng(7)
    trace = [
        (
            float(t),
            corpus.sample(int(rng.integers(16, 80)), seed=4000 + i),
            int(rng.integers(8, 32)),
        )
        for i, t in enumerate(np.cumsum(rng.exponential(mean_gap_s, n_req)))
    ]

    for gname, gcfg in (
        ("full", None),
        ("griffin50", GriffinConfig(sparsity=0.5, per_shard_topk=False)),
    ):
        tracer = bench_tracer()
        srv = PagedServer(cfg, params, gcfg=gcfg, page_size=16, num_pages=64,
                          n_slots=4, prefill_chunk=32, max_len=128,
                          tracer=tracer)
        t0 = time.perf_counter()
        pending = list(trace)
        rid = 0
        while pending or srv.sched.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                _, prompt, gen = pending.pop(0)
                srv.submit(prompt, max_new=gen, rid=rid)
                rid += 1
            if not srv.step() and pending:
                time.sleep(max(0.0, pending[0][0] - (time.perf_counter() - t0)))
        dt = time.perf_counter() - t0
        m = srv.metrics.summary()
        emit(
            f"serving_poisson_{gname}", dt * 1e6,
            f"n={n_req} tok/s={m['tokens_per_sec']:.1f} "
            f"ttft_p50={m['ttft_p50_s']:.3f}s ttft_p95={m['ttft_p95_s']:.3f}s "
            f"tpot_p50={m['tpot_p50_s'] * 1e3:.1f}ms "
            f"occupancy={m['pool_occupancy_mean']:.2f} "
            f"preempt={m['preemptions']:.0f} "
            f"decode_batch={m['decode_batch_mean']:.2f}",
        )
        save_trace(f"serving_{gname}", tracer)


# ---------------------------------------------------------------------------
# Speculative: self-speculative decoding with GRIFFIN draft experts
# ---------------------------------------------------------------------------

def bench_speculative(smoke: bool = False) -> None:
    """GRIFFIN-draft speculative decoding vs vanilla dense decode.

    Two sections, because speculative decoding's win condition is a
    *memory-bound* decode (the paper's regime: weight reads dominate, so
    verifying k+1 tokens costs about one token and the 50%-FF draft pass
    is ~0.55x a dense step).  The tiny trained char-LM is the opposite
    regime — XLA:CPU per-program overhead (~ms) dominates, every extra
    program body costs the same as a dense step, so speculation cannot
    beat dense there no matter how good acceptance is.  We therefore
    split the signals:

    * Section A ``tiny`` — the trained tinylm under a 4-slot serving
      trace.  This is where quality signals live: greedy speculative
      output must be token-identical to dense in BOTH spec impls
      (``fused`` lax.scan draft program and the ``per_token`` legacy
      host loop kept as a differential oracle), real acceptance rates
      from a trained model, adaptive-k trajectories, and the
      prefill-interleave TTFT bound (spec ttft_p50 <= 1.25x dense,
      asserted on the full run).
    * Section B ``membound`` — a wide random-init model (2 layers,
      d_ff 8192: ~57M params, fp32) decoded at batch 1, where a decode
      step actually streams ~230 MB of weights.  This is where the
      wall-clock bar lives: the full run asserts fused griffin_draft
      >= 1.3x dense tokens/sec at equal generated tokens (random-init
      outputs are degenerate text, but identity still must hold — the
      draft/verify/rollback machinery is exercised bit-for-bit).

    Every server is warmed up with fixed-seed requests first and timed
    after ``reset_metrics()``, so JIT compiles (seconds per program) do
    not pollute steady-state throughput or TTFT.
    """
    from repro.configs.base import ModelConfig
    from repro.data.pipeline import SyntheticCorpus
    from repro.serving.server import PagedServer

    spec_k = 4
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)
    modes = {
        "dense": dict(gcfg=None, spec_k=0),
        "griffin_draft": dict(gcfg=gcfg, spec_k=spec_k),
        "griffin_draft_legacy": dict(gcfg=gcfg, spec_k=spec_k,
                                     spec_impl="per_token"),
    }

    def run_trace(cfg, params, mode_kw, prompts, max_new, *, warmup,
                  warmup_new, tracer=None, **server_kw):
        # warmup prompts are FIXED per section (identical across modes):
        # drain() reports every finished request cumulatively, so the
        # warmup rids land in the identity comparison too — harmless
        # only because each mode saw the exact same warmup trace.
        srv = PagedServer(cfg, params, tracer=tracer, **server_kw,
                          **mode_kw)
        for j, p in enumerate(warmup):
            srv.submit(p, max_new=warmup_new, rid=100_000 + j)
        srv.drain()
        srv.reset_metrics()
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            srv.submit(p, max_new=max_new, rid=i)
        fin = srv.drain()
        wall = time.perf_counter() - t0
        outs = {rid: fin[rid] for rid in range(len(prompts))}
        m = srv.metrics.summary()
        summary = {
            "wall_s": wall,
            "tokens_per_sec": m["tokens_per_sec"],
            "ttft_p50_s": m["ttft_p50_s"],
            "ttft_p95_s": m["ttft_p95_s"],
            "tpot_p50_s": m["tpot_p50_s"],
            "acceptance_rate": m["acceptance_rate"],
            "tokens_per_verify": m["tokens_per_verify"],
            "spec_rounds": m["spec_rounds"],
            "spec_capped_rounds": m["spec_capped_rounds"],
            "draft_k_mean": m["draft_k_mean"],
            "generated_tokens": m["generated_tokens"],
        }
        return outs, summary

    # --- Section A: trained tinylm serving trace (quality + TTFT) ---
    cfg, params = trained_tiny(steps=120 if smoke else 500)
    corpus = SyntheticCorpus(vocab=cfg.vocab_size, seed=0)
    n_req = 4 if smoke else 12
    max_new = 12 if smoke else 32
    rng = np.random.default_rng(17)
    prompts = [corpus.sample(int(rng.integers(24, 64)), seed=5000 + i)
               for i in range(n_req)]
    warmup = [corpus.sample(64, seed=901), corpus.sample(40, seed=902)]

    outputs, summaries = {}, {}
    for mode, mode_kw in modes.items():
        tracer = bench_tracer()
        outputs[mode], summaries[mode] = run_trace(
            cfg, params, mode_kw, prompts, max_new,
            warmup=warmup, warmup_new=40, tracer=tracer,
            page_size=16, num_pages=96, n_slots=4, prefill_chunk=32,
            max_len=128)
        s = summaries[mode]
        emit(
            f"speculative_{mode}", s["wall_s"] * 1e6,
            f"n={n_req} tok/s={s['tokens_per_sec']:.1f} "
            f"acc={s['acceptance_rate']:.3f} "
            f"tok_per_verify={s['tokens_per_verify']:.2f} "
            f"k_mean={s['draft_k_mean']:.2f} "
            f"ttft_p50={s['ttft_p50_s']:.3f}s "
            f"tpot_p50={s['tpot_p50_s'] * 1e3:.1f}ms",
        )
        save_trace(f"speculative_{mode}", tracer)
    identical = outputs["dense"] == outputs["griffin_draft"]
    fused_vs_legacy = outputs["griffin_draft"] == outputs["griffin_draft_legacy"]
    tiny_speedup = (summaries["griffin_draft"]["tokens_per_sec"]
                    / summaries["dense"]["tokens_per_sec"])
    ttft_ratio = (summaries["griffin_draft"]["ttft_p50_s"]
                  / max(summaries["dense"]["ttft_p50_s"], 1e-9))
    emit("speculative_greedy_parity", 0.0,
         f"token_identical={identical} fused_vs_legacy={fused_vs_legacy} "
         f"tiny_speedup={tiny_speedup:.2f}x ttft_ratio={ttft_ratio:.2f}x")

    # --- Section B: memory-bound wide model (the wall-clock bar) ---
    wcfg = ModelConfig(
        name="membound", family="dense", num_layers=2,
        d_model=512 if smoke else 1024, num_heads=8, num_kv_heads=4,
        head_dim=64 if smoke else 128, d_ff=4096 if smoke else 8192,
        vocab_size=256, activation="swiglu", tie_embeddings=True,
        max_seq_len=1024, dtype="float32", remat=False, griffin=True)
    wparams = decoder.init_params(wcfg, jax.random.PRNGKey(0))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(wparams))
    wrng = np.random.default_rng(7)
    wprompts = [wrng.integers(0, wcfg.vocab_size, size=s).astype(np.int32)
                for s in ((24, 40) if smoke else (24, 40, 32))]
    wwarm = [wrng.integers(0, wcfg.vocab_size, size=48).astype(np.int32)]
    wmax_new = 10 if smoke else 16

    woutputs, wsummaries = {}, {}
    for mode, mode_kw in modes.items():
        woutputs[mode], wsummaries[mode] = run_trace(
            wcfg, wparams, mode_kw, wprompts, wmax_new,
            warmup=wwarm, warmup_new=20,
            page_size=16, num_pages=64, n_slots=1, prefill_chunk=32,
            max_len=128)
        s = wsummaries[mode]
        emit(
            f"speculative_membound_{mode}", s["wall_s"] * 1e6,
            f"params={n_params / 1e6:.1f}M tok/s={s['tokens_per_sec']:.2f} "
            f"acc={s['acceptance_rate']:.3f} "
            f"tok_per_verify={s['tokens_per_verify']:.2f}",
        )
    w_identical = woutputs["dense"] == woutputs["griffin_draft"]
    w_fused_vs_legacy = (woutputs["griffin_draft"]
                         == woutputs["griffin_draft_legacy"])
    speedup = (wsummaries["griffin_draft"]["tokens_per_sec"]
               / wsummaries["dense"]["tokens_per_sec"])
    emit("speculative_membound_parity", 0.0,
         f"token_identical={w_identical} "
         f"fused_vs_legacy={w_fused_vs_legacy} "
         f"speedup_vs_dense={speedup:.2f}x")

    record("spec_k", spec_k)
    record("smoke", bool(smoke))
    record("modes", summaries)
    record("token_identical", bool(identical and w_identical))
    record("fused_vs_legacy_identical",
           bool(fused_vs_legacy and w_fused_vs_legacy))
    record("tiny_speedup_vs_dense", float(tiny_speedup))
    record("ttft_p50_ratio_vs_dense", float(ttft_ratio))
    record("membound", {
        "params_m": n_params / 1e6,
        "d_model": wcfg.d_model, "d_ff": wcfg.d_ff,
        "num_layers": wcfg.num_layers,
        "modes": wsummaries,
    })
    record("speedup_vs_dense", float(speedup))
    assert identical and w_identical, (
        "greedy speculative decode diverged from dense decode"
    )
    assert fused_vs_legacy and w_fused_vs_legacy, (
        "fused draft scan diverged from the per-token differential oracle"
    )
    if not smoke:
        assert speedup >= 1.3, (
            f"fused speculative decode only {speedup:.2f}x dense in the "
            f"memory-bound regime (acceptance bar is 1.3x)"
        )
        assert ttft_ratio <= 1.25, (
            f"spec-mode ttft_p50 {ttft_ratio:.2f}x dense (bar is 1.25x); "
            f"prefill-interleave cap regressed"
        )


# ---------------------------------------------------------------------------
# Prefix cache: shared-prefix reuse under a Zipf-shared Poisson trace
# ---------------------------------------------------------------------------

def bench_prefix(smoke: bool = False) -> None:
    """Shared-prefix paged-KV reuse (radix cache + COW) vs cold serving.

    The trace models chat traffic: every prompt is one of a few system
    prompts (picked Zipf-distributed, so one dominates) plus a unique
    user suffix, arriving Poisson.  The same trace runs through a cold
    server (``prefix_cache=False``) and a prefix-warm one (cache
    pre-populated by one request per system prompt); outputs must be
    token-identical.  Reported per mode: tokens/sec, TTFT p50/p95,
    prefix hit rate, saved prefill tokens, COW copies — plus the
    ISSUE's headline number, TTFT p50 of *prefix-hit* requests vs the
    cold p50 (each saved chunk is a whole model call, so hits see
    first tokens sooner).
    """
    from repro.data.pipeline import SyntheticCorpus
    from repro.serving.server import PagedServer

    cfg, params = trained_tiny(steps=120 if smoke else 500)
    corpus = SyntheticCorpus(vocab=cfg.vocab_size, seed=0)
    n_sys, n_req = 3, (10 if smoke else 24)
    # moderate load: arrivals must not saturate the decode slots, or
    # TTFT is all backlog wait and the prefill savings drown in it
    mean_gap_s = 0.08 if smoke else 0.3
    rng = np.random.default_rng(23)
    # system prompts: multiples of prefill_chunk so the shared head is
    # fully covered by chunk-boundary trie nodes; long enough that a
    # hit skips 3 of ~4 prefill chunks (each chunk is one model call)
    sys_prompts = [corpus.sample(96, seed=7000 + i) for i in range(n_sys)]
    zipf = 1.0 / np.arange(1, n_sys + 1) ** 1.5
    zipf /= zipf.sum()
    trace = [
        (
            float(t),
            np.concatenate([
                sys_prompts[int(rng.choice(n_sys, p=zipf))],
                corpus.sample(int(rng.integers(4, 16)), seed=8000 + i),
            ]),
            int(rng.integers(6, 16)),
        )
        for i, t in enumerate(np.cumsum(rng.exponential(mean_gap_s, n_req)))
    ]

    outputs, summaries = {}, {}
    for mode, pc in (("cold", False), ("prefix", True)):
        tracer = bench_tracer()
        srv = PagedServer(cfg, params, gcfg=GriffinConfig(
            sparsity=0.5, per_shard_topk=False), page_size=16, num_pages=96,
            n_slots=4, prefill_chunk=32, max_len=128, prefix_cache=pc,
            tracer=tracer)
        for j, sp in enumerate(sys_prompts):  # warm-up (no-op when cold)
            srv.submit(sp, max_new=2, rid=9000 + j)
        srv.drain()
        t0 = time.perf_counter()
        pending = list(trace)
        rid = 0
        while pending or srv.sched.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                _, prompt, gen = pending.pop(0)
                srv.submit(prompt, max_new=gen, rid=rid)
                rid += 1
            if not srv.step() and pending:
                time.sleep(max(0.0, pending[0][0] - (time.perf_counter() - t0)))
        wall = time.perf_counter() - t0
        outputs[mode] = {r: t for r, t in srv.drain().items() if r < 9000}
        m = srv.metrics.summary()
        from repro.serving.metrics import percentile

        hit_ttfts = [r.ttft for r in srv.metrics.requests.values()
                     if r.rid < 9000 and r.prefix_hit_tokens > 0
                     and r.ttft is not None]
        summaries[mode] = {
            "wall_s": wall,
            "tokens_per_sec": m["tokens_per_sec"],
            "ttft_p50_s": m["ttft_p50_s"],
            "ttft_p95_s": m["ttft_p95_s"],
            "ttft_hit_p50_s": percentile(hit_ttfts, 50),
            "prefix_hit_rate": m["prefix_hit_rate"],
            "saved_prefill_tokens": m["saved_prefill_tokens"],
            "cow_copies": m["cow_copies"],
            "shared_pages_mean": m["shared_pages_mean"],
            "preemptions": m["preemptions"],
        }
        emit(
            f"prefix_{mode}", wall * 1e6,
            f"n={n_req} tok/s={m['tokens_per_sec']:.1f} "
            f"ttft_p50={m['ttft_p50_s']:.3f}s "
            f"hit_rate={m['prefix_hit_rate']:.2f} "
            f"saved_tokens={m['saved_prefill_tokens']:.0f} "
            f"cow={m['cow_copies']:.0f}",
        )
        save_trace(f"prefix_{mode}", tracer)
    identical = outputs["cold"] == outputs["prefix"]
    hit_p50 = summaries["prefix"]["ttft_hit_p50_s"]
    cold_p50 = summaries["cold"]["ttft_p50_s"]
    emit("prefix_hit_ttft_vs_cold", 0.0,
         f"hit_p50={hit_p50:.3f}s cold_p50={cold_p50:.3f}s "
         f"token_identical={identical}")
    record("smoke", bool(smoke))
    record("modes", summaries)
    record("token_identical", bool(identical))
    record("hit_ttft_p50_below_cold", bool(hit_p50 < cold_p50))
    assert identical, "prefix-warm serving diverged from cold serving"
    # the timing claim is asserted only on the full trace: the smoke
    # trace (CI, shared runners) is small enough that a noisy-neighbor
    # stall could flip a wall-clock comparison with no code defect —
    # there it is recorded (hit_ttft_p50_below_cold), not enforced
    if not smoke:
        assert hit_p50 < cold_p50, (hit_p50, cold_p50)


# ---------------------------------------------------------------------------
# Sharded serving: shard_map tensor parallelism over an emulated mesh
# ---------------------------------------------------------------------------

def bench_sharded(smoke: bool = False) -> None:
    """Tensor-parallel paged serving vs the single-device oracle
    (distributed/tp.py), on the trained tiny TP model.

    Runs in a subprocess with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the flag
    must precede jax init; see ``benchmarks/sharded_child.py`` for the
    measured trace).  Asserted claims: greedy output token-identical to
    single-device for every (spec_k, model-axis) case, and per-shard
    KV-pool bytes exactly 1/N of the single-device pool (KV-head-axis
    sharding).  CPU-emulated wall clocks are overhead measurements, not
    the TPU speedup story — the memory ∝ 1/N number is the
    hardware-independent signal.
    """
    import os
    import subprocess

    # warm the checkpoint cache here, with the full CPU thread pool:
    # inside the child the 8 emulated devices each get 1/8 of the
    # threads, which makes first-use training needlessly slow
    trained_tiny(120 if smoke else 500, arch="tinylm-tp")

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the child sets its own device count
    # the child is a CPU emulation: it must never reach for an
    # accelerator, which this process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, str(Path(__file__).parent / "sharded_child.py")]
    if smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=3000)
    tail = "\n".join((r.stdout + r.stderr).splitlines()[-30:])
    assert r.returncode == 0, f"sharded_child failed:\n{tail}"
    # literal, not imported: importing sharded_child would run its
    # module body, which force-sets the 8-device XLA_FLAGS process-wide
    marker = "BENCH_SHARDED_JSON:"
    line = next(l for l in r.stdout.splitlines() if l.startswith(marker))
    payload = json.loads(line[len(marker):])

    all_identical = True
    for c in payload["cases"]:
        n, sk = c["model_axis"], c["spec_k"]
        all_identical &= c["token_identical"]
        shrink = c["pool_bytes_single"] / c["pool_bytes_per_shard"]
        emit(f"sharded_model{n}_spec{sk}", c["wall_sharded_s"] * 1e6,
             f"single_wall={c['wall_single_s']:.2f}s "
             f"tok/s={c['tokens_per_sec_sharded']:.1f} "
             f"pool_bytes/shard={c['pool_bytes_per_shard']} "
             f"(1/{shrink:.0f} of single) "
             f"token_identical={c['token_identical']} "
             f"preempt={c['preemptions']:.0f} "
             f"prefix_hit_rate={c['prefix_hit_rate']:.2f}")
        assert c["pool_bytes_per_shard"] * n == c["pool_bytes_single"], c
    record("smoke", payload["smoke"])
    record("arch", payload["arch"])
    record("train_steps", payload["train_steps"])
    record("cases", payload["cases"])
    record("token_identical", bool(all_identical))
    record("pool_bytes_shrink_1_over_n", True)
    assert all_identical, "sharded serving diverged from single-device"


# ---------------------------------------------------------------------------
# Observability: tracing/metrics/flocking overhead on the serving path
# ---------------------------------------------------------------------------

def bench_obs(smoke: bool = False) -> None:
    """Observability overhead: the same deterministic drain with hooks
    off, with span tracing + bounded metrics on, and with the periodic
    dense flocking probe on top.

    One server per mode is built once (compiles outside the timed
    region) and drained repeatedly; the reported wall time is the
    median over repeats of submit-all-upfront drains, so the
    enabled-vs-disabled delta is hook cost, not jit or arrival noise.
    Asserted claims: outputs token-identical across all three modes on
    every repeat (hooks must not perturb serving), the traced run's
    Chrome trace and Prometheus exposition validate cleanly, and —
    full runs only, same wall-clock-noise policy as bench_prefix —
    traced overhead < 3%.  The flocking mode is *expected* to cost
    more (each probe is a real dense decode step every N ticks); its
    overhead is recorded, not bounded.
    """
    from repro.data.pipeline import SyntheticCorpus
    from repro.obs.export import chrome_trace, validate_chrome_trace
    from repro.obs.registry import validate_prometheus_text
    from repro.obs.trace import Tracer
    from repro.serving.server import PagedServer

    cfg, params = trained_tiny(steps=120 if smoke else 500)
    corpus = SyntheticCorpus(vocab=cfg.vocab_size, seed=0)
    n_req = 4 if smoke else 12
    max_new = 10 if smoke else 24
    repeats = 3 if smoke else 5
    flocking_every = 4
    rng = np.random.default_rng(29)
    prompts = [corpus.sample(int(rng.integers(24, 64)), seed=6000 + i)
               for i in range(n_req)]
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)

    modes = {
        "off": dict(tracer=None, flocking_every=0),
        "traced": dict(tracer=Tracer(), flocking_every=0),
        "flocking": dict(tracer=Tracer(), flocking_every=flocking_every),
    }
    servers, walls, outputs = {}, {}, {}
    for mode, kwargs in modes.items():
        srv = PagedServer(cfg, params, gcfg=gcfg, page_size=16,
                          num_pages=96, n_slots=4, prefill_chunk=32,
                          max_len=128, **kwargs)
        servers[mode] = srv
        walls[mode] = []
        outputs[mode] = []
        for rep in range(repeats + 1):  # rep 0 = warmup (jit compiles)
            base = rep * 1000
            t0 = time.perf_counter()
            for i, p in enumerate(prompts):
                srv.submit(p, max_new=max_new, rid=base + i)
            srv.drain()
            wall = time.perf_counter() - t0
            out = {r.rid - base: r.generated
                   for r in srv.sched.finished.values()
                   if base <= r.rid < base + n_req}
            if rep:
                walls[mode].append(wall)
                outputs[mode].append(out)
        med = float(np.median(walls[mode]))
        toks = sum(len(v) for v in outputs[mode][0].values())
        emit(f"obs_{mode}", med * 1e6,
             f"n={n_req} repeats={repeats} tok/s={toks / med:.1f} "
             f"wall_min={min(walls[mode]):.3f}s "
             f"wall_max={max(walls[mode]):.3f}s")

    identical = all(outputs[m] == outputs["off"] for m in modes)
    med = {m: float(np.median(walls[m])) for m in modes}
    overhead = {m: med[m] / med["off"] - 1.0 for m in ("traced", "flocking")}
    emit("obs_overhead", 0.0,
         f"traced={overhead['traced']:+.2%} "
         f"flocking={overhead['flocking']:+.2%} "
         f"token_identical={identical}")

    # the traced run's artifacts must validate (schema + nesting +
    # async pairing; Prometheus exposition syntax + histogram shape)
    tr = servers["traced"].tracer
    trace_errs = validate_chrome_trace(chrome_trace(tr))
    prom_errs = validate_prometheus_text(
        servers["traced"].metrics.prometheus_text())
    emit("obs_artifacts_valid", float(len(tr.events)),
         f"trace_events={len(tr.events)} trace_errors={len(trace_errs)} "
         f"prom_errors={len(prom_errs)}")
    save_trace("obs_traced", tr)

    record("smoke", bool(smoke))
    record("n_requests", n_req)
    record("repeats", repeats)
    record("flocking_every", flocking_every)
    record("walls_s", walls)
    record("median_wall_s", med)
    record("overhead", overhead)
    record("token_identical", bool(identical))
    record("trace_events", len(tr.events))
    record("trace_errors", trace_errs)
    record("prom_errors", prom_errs)
    record("traced_overhead_below_3pct", bool(overhead["traced"] < 0.03))
    assert identical, "observability hooks perturbed served tokens"
    assert not trace_errs, trace_errs
    assert not prom_errs, prom_errs
    # the wall-clock bound is asserted only on the full run: the smoke
    # drain (CI, shared runners) is short enough that a noisy-neighbor
    # stall could flip a <3% comparison with no code defect — there it
    # is recorded (traced_overhead_below_3pct), not enforced
    if not smoke:
        assert overhead["traced"] < 0.03, overhead


# ---------------------------------------------------------------------------
# Serving-SLO: goodput/TTFT/shed under calibrated 1x and 2x overload
# ---------------------------------------------------------------------------

def bench_serving_slo(smoke: bool = False) -> None:
    """Async frontend under closed-loop chat load at 1x and 2x the
    calibrated capacity (serving/frontend.py + serving/loadgen.py).

    Three stages:

    1. **calibrate** — drain a batch synchronously to measure this
       host's service rate (requests/s) and baseline TTFT; the load
       points and the per-class TTFT deadlines are derived from these,
       so the benchmark measures the *policy* (admission, EDF, shed)
       rather than the host's absolute speed.
    2. **load** — run the Zipf x Poisson x long-tail multi-turn trace
       through the frontend at 1x and 2x calibrated capacity on the
       real clock, plus a ``1x_spec`` point (same 1x trace with
       self-speculative decode on).  Reported per point: goodput under
       SLO (tokens from SLO-met completions per second), TTFT p50/p99,
       shed+reject rate, SLO-met rate.  ``1x_spec`` must keep ttft_p50
       within 1.25x of the 1x point (asserted, with a scheduler-noise
       floor) — the prefill-interleave cap is what makes that hold.
    3. **oracle** — every finished turn's (prompt, max_new) replays
       through a fresh synchronous ``PagedServer`` drain; streamed
       tokens must match token-for-token (``token_identical``).  The
       two decode semantics get separate oracles: 1x/2x streams are
       GRIFFIN-*pruned* generation (lossy by design) and replay
       through a pruned server, while ``1x_spec`` streams are
       dense-*exact* (speculation drafts with the pruned weights but
       commits only dense-verified tokens) and replay through a fully
       dense ``gcfg=None`` server — re-asserting the spec==dense
       invariant end-to-end through the async frontend.

    Correctness (token identity) is asserted always; load-shape
    indicators (shed monotonicity, goodput saturation ratio) are
    recorded but never asserted — the closed loop self-throttles (a
    shed turn ends its session), so those wobble at bench trace sizes
    without any code defect.
    """
    from repro.serving.frontend import ServingFrontend
    from repro.serving.loadgen import chat_sessions, run_closed_loop
    from repro.serving.server import PagedServer

    cfg, params = trained_tiny(steps=120 if smoke else 500)
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=False)

    def make_server(tracer=None, spec=False):
        # spec=True turns on self-speculative decode (fused draft scan +
        # adaptive k) — the 1x_spec point checks that speculation does
        # not inflate TTFT under live prefill load (the
        # prefill-interleave cap bounds draft work while chunks pend)
        kw = dict(spec_k=4) if spec else {}
        return PagedServer(cfg, params, gcfg=gcfg, page_size=16,
                           num_pages=128, n_slots=4, prefill_chunk=32,
                           max_len=192, prefix_cache=True, tracer=tracer,
                           **kw)

    # -- 1. calibrate service capacity -------------------------------------
    # warmup drain first (jit compile), then an unloaded pair for the
    # queue-free TTFT baseline, then a saturated batch for requests/s —
    # conflating those would fold compile + queue wait into the
    # deadlines and no load point would ever shed
    rng = np.random.default_rng(3)
    calib = make_server()
    for i in range(2):
        calib.submit(rng.integers(0, cfg.vocab_size, size=40), max_new=4,
                     rid=9000 + i)
    calib.drain()
    for i in range(2):
        calib.submit(rng.integers(0, cfg.vocab_size, size=40), max_new=10,
                     rid=9100 + i)
    calib.drain()
    unloaded = [r.ttft for r in calib.metrics.requests.values()
                if r.rid >= 9100 and r.ttft is not None]
    ttft_base = max(float(np.median(unloaded)), 1e-3)
    n_cal = 6 if smoke else 12
    for i in range(n_cal):
        calib.submit(rng.integers(0, cfg.vocab_size, size=40), max_new=10,
                     rid=i)
    t0 = time.perf_counter()
    calib.drain()
    cal_wall = time.perf_counter() - t0
    capacity_rps = n_cal / cal_wall
    # deadlines with real headroom over the unloaded baseline (floors
    # absorb scheduler-noise blips on shared runners): interactive
    # sheds under sustained overload, standard rarely does
    deadlines = {"interactive": max(8.0 * ttft_base, 0.25),
                 "standard": max(24.0 * ttft_base, 0.75),
                 "batch": None}
    emit("serving_slo_calibration", cal_wall * 1e6,
         f"capacity={capacity_rps:.2f}req/s ttft_base={ttft_base:.3f}s")

    # -- 2. closed-loop load at 1x and 2x ----------------------------------
    n_sessions = 8 if smoke else 20
    mean_turns = 2.0  # E[uniform{1..3}]
    points = {}
    # two stream pools: 1x/2x decode GRIFFIN-pruned (lossy by design),
    # 1x_spec commits only dense-verified tokens (dense-exact) — the
    # same (prompt, max_new) legitimately yields different tokens
    # across the two semantics, so each pool gets its own oracle below
    streams, spec_streams = {}, {}
    for label, factor, spec in (("1x", 1.0, False), ("2x", 2.0, False),
                                ("1x_spec", 1.0, True)):
        pool = spec_streams if spec else streams
        tracer = bench_tracer()
        srv = make_server(tracer, spec=spec)
        # jit-warm this instance before the measured window, or the
        # first arrivals eat the compile stall and shed spuriously
        srv.submit(rng.integers(0, cfg.vocab_size, size=40), max_new=4,
                   rid=9500)
        srv.drain()
        fe = ServingFrontend(srv, max_pending=32, queue_depth=8)
        sessions = chat_sessions(
            n_sessions, rate=capacity_rps * factor / mean_turns,
            seed=29, vocab=cfg.vocab_size, n_system=3, system_len=48,
            max_turns=3, gen_median=6.0, gen_cap=16,
            think_mean_s=0.5 / capacity_rps, deadlines=deadlines)
        res = run_closed_loop(fe, sessions, clock=fe.clock)
        s = res.summary()
        s["frontend"] = fe.summary()
        s["engine_sheds"] = srv.metrics.shed_aborts
        s["cancel_latency_p95_s"] = \
            srv.metrics.summary()["cancel_latency_p95_s"]
        points[label] = s
        for key, toks in res.identity_pairs().items():
            if key in pool:
                assert pool[key] == toks, "cross-point stream mismatch"
            pool[key] = toks
        emit(f"serving_slo_{label}", s["wall_s"] * 1e6,
             f"goodput={s['goodput_tokens_per_sec']:.1f}tok/s "
             f"ttft_p99={s['ttft_p99_s']:.3f}s "
             f"shed_rate={s['shed_rate']:.2f} "
             f"slo_met={s['slo_met_rate']:.2f}")
        save_trace(f"serving_slo_{label}", tracer)

    # -- 3. streamed-vs-drained oracles ------------------------------------
    # pruned streams replay through a pruned server; spec streams are
    # dense-exact, so they replay through a *fully dense* server —
    # the strongest form of the spec==dense invariant, measured through
    # the async frontend rather than a synchronous drain
    oracle = make_server()
    keys = list(streams)
    for i, (prompt, max_new) in enumerate(keys):
        oracle.submit(np.asarray(prompt, np.int32), max_new=max_new, rid=i)
    outs = oracle.drain()
    identical = all(tuple(outs[i]) == streams[keys[i]]
                    for i in range(len(keys)))
    dense_oracle = PagedServer(cfg, params, gcfg=None, page_size=16,
                               num_pages=128, n_slots=4, prefill_chunk=32,
                               max_len=192, prefix_cache=True)
    skeys = list(spec_streams)
    for i, (prompt, max_new) in enumerate(skeys):
        dense_oracle.submit(np.asarray(prompt, np.int32), max_new=max_new,
                            rid=i)
    souts = dense_oracle.drain()
    spec_identical = all(tuple(souts[i]) == spec_streams[skeys[i]]
                         for i in range(len(skeys)))
    emit("serving_slo_identity", 0.0,
         f"streams={len(keys)} token_identical={identical} "
         f"spec_streams={len(skeys)} spec_dense_exact={spec_identical}")

    record("smoke", bool(smoke))
    record("capacity_rps", capacity_rps)
    record("ttft_base_s", ttft_base)
    record("deadlines_s", {k: v for k, v in deadlines.items()})
    record("points", points)
    record("streams_checked", len(keys))
    record("spec_streams_checked", len(skeys))
    record("token_identical", bool(identical))
    record("spec_streams_dense_exact", bool(spec_identical))
    # load-shape indicators are recorded, never asserted: the closed
    # loop self-throttles (a shed turn ends its session), so per-run
    # shed rates wobble at these trace sizes without any code defect
    record("shed_rate_monotone",
           bool(points["2x"]["shed_rate"] >= points["1x"]["shed_rate"]))
    g1 = points["1x"]["goodput_tokens_per_sec"]
    g2 = points["2x"]["goodput_tokens_per_sec"]
    record("goodput_2x_over_1x", g2 / g1 if g1 > 0 else 0.0)
    # speculative decode must not inflate TTFT at equal load: the
    # prefill-interleave cap clamps draft length while prefill chunks
    # pend, so first tokens are not stuck behind k-token spec rounds.
    # The ttft_base floor absorbs scheduler-noise blips at bench sizes.
    spec_ttft = points["1x_spec"]["ttft_p50_s"]
    base_ttft = points["1x"]["ttft_p50_s"]
    spec_bound = max(1.25 * base_ttft, 3.0 * ttft_base)
    record("spec_ttft_p50_s", spec_ttft)
    record("spec_ttft_p50_bound_s", spec_bound)
    assert spec_ttft <= spec_bound, (
        f"spec-mode ttft_p50 {spec_ttft:.3f}s exceeds bound "
        f"{spec_bound:.3f}s (1x p50 {base_ttft:.3f}s, "
        f"base {ttft_base:.3f}s) — prefill-interleave cap regressed"
    )
    assert identical, "streamed tokens diverged from the drain oracle"
    assert spec_identical, (
        "speculative streams diverged from the dense drain oracle"
    )
    assert keys, "no finished streams to verify"
    assert skeys, "no finished speculative streams to verify"


# ---------------------------------------------------------------------------
# Roofline table from dry-run artifacts
# ---------------------------------------------------------------------------

def bench_sparsity_tiers(smoke: bool = False) -> None:
    """Perplexity-vs-throughput frontier of the per-request sparsity
    tiers (``--tier`` on the serving stack, DESIGN.md section 16).

    Serves the trained tiny char-LM once per tier through a
    flocking-derived per-layer profile and measures (a) decode
    throughput — batch 1 (``n_slots=1``): on XLA:CPU the per-program
    overhead at batch 4 nearly erases the compacted-matmul win, and the
    tier mechanism's target regime is memory-bound batch-1 decode — and
    (b) teacher-forced perplexity of each tier's generations under the
    full model.  Asserts the frontier's endpoints: tier 0.25 must beat
    tier 1.0 in decode tokens/sec (the whole point of the knob).  The
    per-layer ``k`` vectors land in the artifact header so trajectory
    comparisons never mix budgets silently.
    """
    from repro.analysis.profile import derive_profile
    from repro.core import griffin as griffin_lib
    from repro.data.pipeline import SyntheticCorpus
    from repro.serving.server import PagedServer

    cfg, params = trained_tiny(steps=120 if smoke else 500)
    corpus = SyntheticCorpus(vocab=cfg.vocab_size, seed=0)
    gcfg = GriffinConfig(sparsity=0.5)

    prof_seqs = eval_sequences(cfg, n=2 if smoke else 4, length=96)
    profile = derive_profile(cfg, params, prof_seqs)

    n_req = 2 if smoke else 6
    max_new = 32 if smoke else 64
    rng = np.random.default_rng(23)
    prompts = [corpus.sample(int(rng.integers(16, 32)), seed=7700 + i)
               for i in range(n_req)]
    warmup = [corpus.sample(24, seed=701)]

    plans = {t: griffin_lib.plan_k_tree(cfg, gcfg, tier=t, profile=profile)
             for t in griffin_lib.TIERS}
    frontier = {}
    for tier in griffin_lib.TIERS:
        srv = PagedServer(cfg, params, gcfg=gcfg, page_size=16,
                          num_pages=64, n_slots=1, prefill_chunk=32,
                          max_len=160, profile=profile, default_tier=tier)
        for j, p in enumerate(warmup):
            srv.submit(p, max_new=8, rid=100_000 + j)
        srv.drain()
        srv.reset_metrics()
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            srv.submit(p, max_new=max_new, rid=i)
        fin = srv.drain()
        wall = time.perf_counter() - t0
        m = srv.metrics.summary()
        decode_tps = 1.0 / max(m["tpot_p50_s"], 1e-9)

        nll = cnt = 0.0
        for i in range(n_req):
            seq = np.concatenate([prompts[i], np.asarray(fin[i])])
            P = len(prompts[i])
            ppl_i = evaluate.generation_ppl(
                params, cfg, jnp.asarray(seq[None]), P, "full")
            nll += np.log(ppl_i) * (len(seq) - P)
            cnt += len(seq) - P
        ppl = float(np.exp(nll / max(cnt, 1)))

        frontier[str(tier)] = {
            "decode_tok_s": decode_tps,
            "tpot_p50_s": m["tpot_p50_s"],
            "tokens_per_sec": m["tokens_per_sec"],
            "generation_ppl": ppl,
            "wall_s": wall,
        }
        emit(f"tier_{tier}", m["tpot_p50_s"] * 1e6,
             f"decode_tok_s={decode_tps:.1f} ppl={ppl:.3f} "
             f"tok_s={m['tokens_per_sec']:.1f}")

    lo = frontier[str(0.25)]["decode_tok_s"]
    hi = frontier[str(1.0)]["decode_tok_s"]
    assert lo > hi, (
        f"tier 0.25 decode tok/s ({lo:.1f}) must beat tier 1.0 ({hi:.1f})"
    )
    record("frontier", frontier)
    record("profile", {p: list(ws) for p, ws in profile.weights})
    set_bench_header(per_layer_k={
        str(t): {path: list(ks) for path, ks in plans[t].items()}
        for t in griffin_lib.TIERS
    })


def bench_roofline_table() -> None:
    art = Path("artifacts/dryrun")
    if not art.exists():
        emit("roofline_table", 0.0, "no dry-run artifacts; run scripts/dryrun_all.sh")
        return
    n = 0
    for f in sorted(art.glob("*_p1.json")):
        rec = json.loads(f.read_text())
        if rec.get("status") != "ok":
            continue
        r = rec["roofline"]
        emit(
            f"roofline_{rec['arch']}_{rec['shape']}",
            r["bound_s"] * 1e6,
            f"dominant={r['dominant']} compute={r['compute_s']:.2e} "
            f"memory={r['memory_s']:.2e} coll={r['collective_s']:.2e} "
            f"useful={r['useful_ratio']:.3f}",
        )
        n += 1
    emit("roofline_cells_ok", float(n), "cells with successful dry-run")


BENCHES = {
    "fig1_2": bench_flocking,
    "table1": bench_table1_classification,
    "table2": bench_table2_generation,
    "fig4": bench_fig4_sparsity,
    "fig5": bench_fig5_prompt_gen,
    "table4": bench_table4_batching,
    "table5": bench_table5_selection,
    "table3": bench_table3_latency,
    "kernels": bench_kernels,
    "decode_attn": bench_decode_attn,
    "serving": bench_serving,
    "speculative": bench_speculative,
    "prefix": bench_prefix,
    "sharded": bench_sharded,
    "obs": bench_obs,
    "serving_slo": bench_serving_slo,
    "sparsity_tiers": bench_sparsity_tiers,
    "roofline": bench_roofline_table,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(BENCHES))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shapes/trace for CI smoke runs")
    ap.add_argument("--kv-dtype", default="fp32",
                    choices=["fp32", "bf16", "int8", "fp8"],
                    help="KV-pool storage dtype for benches that take "
                         "one (decode_attn); quantized dtypes also run "
                         "the error-budget + token-match quality gates")
    ap.add_argument("--out-dir", default=".",
                    help="directory for the BENCH_<name>.json artifacts")
    ap.add_argument("--trace-dir", default=None,
                    help="also write TRACE_<name>.json Chrome traces of "
                         "the serving benchmarks' drains (obs/trace.py)")
    args = ap.parse_args()
    use_compile_cache()
    set_trace_dir(args.trace_dir)
    names = [n.strip() for n in (args.only.split(",") if args.only
                                 else list(BENCHES))]
    print("name,us_per_call,derived")
    drain_results()  # drop anything emitted outside the harness
    for name in names:
        fn = BENCHES[name]
        try:
            kw = {}
            sig = inspect.signature(fn).parameters
            if "smoke" in sig:
                kw["smoke"] = args.smoke
            if "kv_dtype" in sig:
                kw["kv_dtype"] = args.kv_dtype
            fn(**kw)
        finally:
            # persist whatever was emitted even when the bench raises
            # (e.g. the speculative parity assertion): the artifact is
            # the diagnostic for exactly that failure
            rows, extra = drain_results()
            if rows or extra:
                write_bench_json(name, rows, extra, Path(args.out_dir))


if __name__ == "__main__":
    main()
