"""Production serving launcher: prefill -> GRIFFIN select/compact ->
pruned decode, over the paged-KV serving stack (block-table cache +
chunked-prefill scheduler; see serving/server.py).  Families the paged
path doesn't cover (MLA / SSM / RG-LRU / MoE) fall back to the
slot-broadcast ``ContinuousBatcher``.

  PYTHONPATH=src python -m repro.launch.serve --arch tinylm \
      --requests 8 --sparsity 0.5

Tensor-parallel serving (``--mesh model=N``): the paged server runs
shard_mapped over an N-way ``model`` mesh axis — KV pools and the
attention kernel shard along KV heads, GRIFFIN-compacted FF experts
along the (divisible-padded) hidden axis; outputs are token-identical
to the single-device path.  On CPU, emulate devices first:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch tinylm-tp \
      --mesh model=2 --requests 8

``tinylm``/``tinylm-tp`` serve the framework-trained tiny model; every
other arch serves its published config with bf16 weights from a seed.
``--layers N`` cuts the depth only (widths stay published); ``--smoke``
swaps in the reduced same-family config for CPU runs:

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --layers 8 \
      --kernel-backend fused --tier 0.5
"""
import argparse
import time
from typing import List, Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import ASSIGNED_ARCHS, get_config
from repro.core import TIERS, GriffinConfig, SparsityProfile
from repro.data.pipeline import SyntheticCorpus
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.models import decoder
from repro.serving.engine import ContinuousBatcher
from repro.serving.server import PagedServer
from repro.serving.slo import SLO_CLASSES


def parse_mesh(spec: str):
    """``model=N`` -> (axis, N).  Only a 1-D tensor-parallel axis is
    meaningful for the paged server today."""
    try:
        axis, n = spec.split("=")
        return axis.strip(), int(n)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--mesh wants AXIS=N (e.g. model=2), got {spec!r}"
        )


def main(argv: Optional[List[str]] = None) -> Optional[dict]:
    """Serve synthetic requests (or HTTP).  Returns, for a drained
    synthetic run on the paged path, ``{"cfg", "requests" [(prompt,
    max_new)], "results" {rid: tokens}, "seconds", "server"}``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinylm",
                    choices=ASSIGNED_ARCHS + ["tinylm", "tinylm-tp"])
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="cut the depth to N layers (widths stay as "
                         "published)")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced same-family config (tiny "
                         "widths, for CPU runs) instead of the published "
                         "one")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--no-griffin", action="store_true")
    ap.add_argument("--tier", type=float, default=None,
                    choices=list(TIERS),
                    help="per-request sparsity tier: the fraction of FF "
                         "experts every request keeps (1.0 = dense "
                         "path, bit-exact).  Synthetic requests all "
                         "carry it; in --http mode it becomes the "
                         "default for requests that don't send a "
                         "\"tier\" field.  Omit for the legacy global "
                         "--sparsity budget")
    ap.add_argument("--sparsity-profile", default=None, metavar="PATH",
                    help="per-layer expert-budget profile JSON (emit one "
                         "with examples/flocking_analysis.py "
                         "--emit-profile); scales each layer's tier "
                         "budget by its weight.  Requires --tier")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="self-speculative decoding: tokens drafted per "
                         "verify with the GRIFFIN-compacted weights "
                         "(requires GRIFFIN; output stays dense-exact)")
    ap.add_argument("--spec-impl", default="fused",
                    choices=["fused", "per_token"],
                    help="draft-loop implementation: 'fused' runs the "
                         "whole k-token draft + verify round as one "
                         "lax.scan device program (one dispatch + one "
                         "host sync per round); 'per_token' is the "
                         "legacy one-dispatch-per-draft-token host "
                         "loop, kept as a differential oracle (output "
                         "is token-identical either way)")
    ap.add_argument("--no-adaptive-spec", action="store_true",
                    help="pin the draft length at --spec-k instead of "
                         "adapting it per request from the live "
                         "acceptance EWMA")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix paged-KV reuse (radix "
                         "cache + copy-on-write pages; output is "
                         "token-identical either way)")
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "fused", "gather"],
                    help="paged-attention path: the fused Pallas decode "
                         "kernel (kernels/paged_attn.py), the gather-"
                         "then-attend oracle, or auto (fused on TPU, "
                         "gather elsewhere); output is token-identical "
                         "either way")
    ap.add_argument("--kv-dtype", default="fp32",
                    choices=["fp32", "bf16", "int8", "fp8"],
                    help="paged KV-pool storage dtype: fp32 inherits the "
                         "model dtype (token-identical baseline); bf16 "
                         "halves pool bytes; int8/fp8 quantize pages "
                         "with per-page-per-head scales dequantized "
                         "inside the attention kernel (see DESIGN.md "
                         "§15 for the error budget)")
    ap.add_argument("--mesh", type=parse_mesh, default=None,
                    metavar="AXIS=N",
                    help="run the paged server tensor-parallel over an "
                         "N-way mesh axis (e.g. model=2): KV pools + "
                         "fused attention shard along KV heads, GRIFFIN "
                         "experts along the FF hidden axis; output is "
                         "token-identical to single-device serving")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record the drain as structured spans and write "
                         "a Chrome/Perfetto trace.json (open in "
                         "chrome://tracing or ui.perfetto.dev)")
    ap.add_argument("--metrics-snapshot", default=None, metavar="PATH",
                    help="write end-of-drain metrics: .json -> JSON "
                         "snapshot, anything else -> Prometheus text "
                         "exposition")
    ap.add_argument("--flocking-telemetry", type=int, default=0,
                    metavar="N",
                    help="probe GRIFFIN expert-selection stability every "
                         "N decode ticks (Jaccard + angular drift per "
                         "layer; requires GRIFFIN; 0 = off)")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace of the "
                         "drain into DIR (with --trace-out, every tracer "
                         "span is also a TraceAnnotation on the "
                         "profiler's clock)")
    ap.add_argument("--http", default=None, metavar="HOST:PORT",
                    help="serve over HTTP instead of draining synthetic "
                         "requests: POST /v1/generate streams tokens as "
                         "SSE, GET /metrics is Prometheus, GET /healthz "
                         "is liveness (continuous batching + SLO-aware "
                         "admission; see serving/frontend.py)")
    ap.add_argument("--slo-class", default="standard",
                    choices=sorted(SLO_CLASSES),
                    help="default SLO class for requests that do not "
                         "name one (priority + TTFT deadline; expired "
                         "requests that produced nothing are shed)")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.arch in ("tinylm", "tinylm-tp"):
        if args.layers is not None or args.smoke:
            ap.error(f"{args.arch} serves its trained checkpoint as is; "
                     f"--layers/--smoke apply to published archs")
        cfg = get_config(args.arch)
        ckpt_dir = args.ckpt_dir or f"artifacts/models/{args.arch}-s500"
        mgr = CheckpointManager(ckpt_dir, interval=1)
        if mgr.latest_step() is not None:
            state, step = mgr.restore_latest()
            params = jax.tree.map(jax.numpy.asarray, state["params"])
            print(f"[ckpt] loaded {ckpt_dir} (step {step})")
        else:
            params = decoder.init_params(cfg, jax.random.PRNGKey(0))
            print(f"[ckpt] no checkpoint in {ckpt_dir}; serving an "
                  f"UNTRAINED init (train one via benchmarks.common."
                  f"trained_tiny or pass --ckpt-dir)")
    else:
        cfg = get_config(args.arch, smoke=args.smoke)
        if args.layers is not None:
            cfg = cfg.replace(num_layers=args.layers)
        params = decoder.init_params(cfg, jax.random.PRNGKey(0))

    # per_shard_topk inherits the single-sourced default
    # (griffin.DEFAULT_PER_SHARD_TOPK) — inert at tp_shards=1, and the
    # server forces it on under a mesh either way
    gcfg = None if (args.no_griffin or not cfg.griffin or not cfg.has_ffn) \
        else GriffinConfig(sparsity=args.sparsity)
    profile = None
    if args.sparsity_profile is not None:
        if args.tier is None:
            ap.error("--sparsity-profile requires --tier (profiles scale "
                     "tier budgets)")
        profile = SparsityProfile.load(args.sparsity_profile)
        print(f"[profile] {args.sparsity_profile} "
              f"({len(profile.weights)} layer weights, "
              f"arch={profile.arch or '?'})")
    if args.tier is not None and gcfg is None:
        ap.error("--tier requires GRIFFIN (drop --no-griffin)")
    corpus = SyntheticCorpus(vocab=cfg.vocab_size, seed=0)
    rng = np.random.default_rng(0)
    reqs = [
        (corpus.sample(int(rng.integers(16, args.max_len // 2)), seed=500 + rid),
         int(rng.integers(8, 32)))
        for rid in range(args.requests)
    ]

    mode = f"GRIFFIN@{args.sparsity:.0%}" if gcfg else "full model"
    if args.tier is not None:
        mode = f"GRIFFIN tier={args.tier}" + ("+profile" if profile else "")
    if args.spec_k and gcfg is None:
        ap.error("--spec-k requires GRIFFIN (drop --no-griffin)")
    if args.spec_k and not decoder.supports_paged(cfg):
        ap.error(f"--spec-k requires the paged serving path; "
                 f"{cfg.name} falls back to the slot batcher")
    if args.spec_k:
        mode += f"+spec{args.spec_k}"
    obs_flags = (args.trace_out, args.metrics_snapshot,
                 args.flocking_telemetry, args.jax_profile)
    if any(obs_flags) and not decoder.supports_paged(cfg):
        ap.error(f"observability flags need the paged serving path; "
                 f"{cfg.name} falls back to the slot batcher")
    if args.http is not None:
        if not decoder.supports_paged(cfg):
            ap.error(f"--http requires the paged serving path; "
                     f"{cfg.name} falls back to the slot batcher")
        try:
            http_host, http_port = args.http.rsplit(":", 1)
            http_port = int(http_port)
        except ValueError:
            ap.error(f"--http wants HOST:PORT, got {args.http!r}")
    if args.flocking_telemetry and gcfg is None:
        ap.error("--flocking-telemetry requires GRIFFIN "
                 "(drop --no-griffin)")
    tracer = None
    if args.trace_out:
        from repro.obs.trace import Tracer
        tracer = Tracer(annotate_jax=bool(args.jax_profile))
    mesh = None
    if args.mesh is not None:
        axis, n = args.mesh
        if not decoder.supports_paged(cfg):
            ap.error(f"--mesh requires the paged serving path; "
                     f"{cfg.name} falls back to the slot batcher")
        mesh = make_serving_mesh(n, axis)
        mode += f"+tp{n}"
        print(f"[mesh] {axis}={n} over {jax.device_count()} visible "
              f"devices ({jax.default_backend()})")
    if decoder.supports_paged(cfg):
        srv = PagedServer(
            cfg, params, gcfg=gcfg, page_size=args.page_size,
            num_pages=args.num_pages, n_slots=args.slots,
            prefill_chunk=args.prefill_chunk, max_len=args.max_len,
            spec_k=args.spec_k, spec_impl=args.spec_impl,
            adaptive_spec=not args.no_adaptive_spec,
            prefix_cache=not args.no_prefix_cache,
            kernel_backend=args.kernel_backend,
            kv_dtype=args.kv_dtype, mesh=mesh,
            tp_axis=args.mesh[0] if args.mesh else "model",
            tracer=tracer, flocking_every=args.flocking_telemetry,
            profile=profile, default_tier=args.tier,
        )
        if args.http is not None:
            import asyncio

            from repro.serving.frontend import ServingFrontend

            fe = ServingFrontend(srv, default_slo=args.slo_class)
            print(f"[{mode}] http: serving on {http_host}:{http_port} "
                  f"(default SLO class: {args.slo_class})")
            print(f"  POST http://{http_host}:{http_port}/v1/generate  "
                  f'{{"prompt": [1,2,3], "max_new": 16, '
                  f'"slo": "interactive"}}  -> SSE token stream')
            print(f"  GET  http://{http_host}:{http_port}/metrics   "
                  f"(Prometheus)   /healthz (liveness)")
            try:
                asyncio.run(fe.serve_http(http_host, http_port))
            except KeyboardInterrupt:
                pass
            m = srv.metrics.summary()
            s = fe.summary()
            print(f"[{mode}] http: accepted={s['accepted']:.0f} "
                  f"completed={s['completed']:.0f} shed={s['shed']:.0f} "
                  f"slo_met_rate={s['slo_met_rate']:.2f} "
                  f"ttft_p99={s['ttft_p99_s']:.3f}s "
                  f"steps={m['steps']:.0f}")
            return
        for rid, (prompt, gen) in enumerate(reqs):
            srv.submit(prompt, max_new=gen, rid=rid)
        if args.jax_profile:
            jax.profiler.start_trace(args.jax_profile)
        t0 = time.perf_counter()
        results = srv.drain()
        dt = time.perf_counter() - t0
        if args.jax_profile:
            jax.profiler.stop_trace()
            print(f"[obs] jax profile -> {args.jax_profile}")
        total = sum(len(v) for v in results.values())
        m = srv.metrics.summary()
        print(f"[{mode}] paged: served {args.requests} requests / {total} "
              f"tokens in {dt:.2f}s ({total/dt:.1f} tok/s, {args.slots} slots)")
        print(f"  ttft p50={m['ttft_p50_s']:.3f}s p95={m['ttft_p95_s']:.3f}s "
              f"occupancy={m['pool_occupancy_mean']:.0%} "
              f"preemptions={m['preemptions']:.0f}")
        if not args.no_prefix_cache:
            print(f"  prefix: hit_rate={m['prefix_hit_rate']:.2f} "
                  f"saved_tokens={m['saved_prefill_tokens']:.0f} "
                  f"cow={m['cow_copies']:.0f} "
                  f"shared_pages={m['shared_pages_mean']:.1f}")
        if args.spec_k:
            print(f"  spec: acceptance={m['acceptance_rate']:.3f} "
                  f"tokens/verify={m['tokens_per_verify']:.2f} "
                  f"rounds={m['spec_rounds']:.0f} "
                  f"k_mean={m['draft_k_mean']:.2f} "
                  f"capped_rounds={m['spec_capped_rounds']:.0f}")
        if args.flocking_telemetry and srv.flocking is not None \
                and srv.flocking.last:
            vals = list(srv.flocking.last.values())
            jac = float(np.mean([v["jaccard"] for v in vals]))
            ang = float(np.mean([v["angular"] for v in vals]))
            print(f"  flocking: jaccard={jac:.3f} angular={ang:.3f} "
                  f"({len(vals)} requests probed every "
                  f"{args.flocking_telemetry} ticks)")
        if tracer is not None:
            from repro.obs.export import write_trace
            path = write_trace(tracer, args.trace_out,
                               meta={"mode": mode,
                                     "requests": args.requests})
            print(f"[obs] trace ({len(tracer.events)} events) -> {path}")
        if args.metrics_snapshot:
            path = srv.metrics.write_snapshot(args.metrics_snapshot)
            print(f"[obs] metrics snapshot -> {path}")
        return {"cfg": cfg, "requests": reqs, "results": results,
                "seconds": dt, "server": srv}

    cb = ContinuousBatcher(cfg, params, n_slots=args.slots,
                           max_len=args.max_len, gcfg=gcfg)
    for rid, (prompt, gen) in enumerate(reqs):
        cb.submit(prompt, max_new=gen, rid=rid)
    t0 = time.perf_counter()
    results = cb.run()
    dt = time.perf_counter() - t0
    total = sum(len(v) for v in results.values())
    print(f"[{mode}] slots: served {args.requests} requests / {total} tokens "
          f"in {dt:.2f}s ({total/dt:.1f} tok/s, {args.slots} slots)")


if __name__ == "__main__":
    main()
