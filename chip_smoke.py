"""Chip smoke run: the paged GRIFFIN serving path on a TPU, at published
widths.

Serves ``yi-9b`` (d_model 4096, 32 query and 4 KV heads of 128, d_ff
11008, vocab 64000; bf16 weights from a seed) with its depth cut from 48
to 8 layers, through ``repro.launch.serve.main`` with the fused Pallas
paged-attention kernel compiled by Mosaic:

  python chip_smoke.py                 # one chip
  python chip_smoke.py --mesh model=4  # four chips only

One chip: one fused ``paged_attn`` call at yi-9b shapes against the
``kernels/ref.py`` oracle, then 8 requests (prompts of 16-511 tokens,
8-31 new tokens each) served once at GRIFFIN tier 0.5 and once dense.
``--mesh model=4``: yi-9b at the same widths with fp32 weights and 4
layers; 8 requests (prompts of 128-512 tokens, 32 new tokens each)
served tensor-parallel over four chips and on one chip with the same
balanced GRIFFIN selection (``GriffinConfig(tp_shards=4)``), both at
"highest" matmul precision.  Prefill logits must agree within
``LOGIT_TOL`` and every greedy token must be identical; the pools and
FF union buffers must be spread over all four devices.

Exits non-zero, with no result line, where JAX finds no TPU.  Wall
times it prints come from a smoke run, not a benchmark.  The last line
of its output is ``{"ok": true, "device": {...}}``.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH, LAYERS = "yi-9b", 8
#: 8 synthetic requests: prompts of 16-511 tokens, 8-31 new tokens each
SERVE_ARGS = [
    "--arch", ARCH, "--layers", str(LAYERS), "--kernel-backend", "fused",
    "--requests", "8", "--slots", "4", "--max-len", "1024",
    "--page-size", "16", "--num-pages", "512",
]
#: |fused ctx - oracle ctx| on unit-Gaussian bf16 q/K/V (both attend in
#: fp32; the oracle at "highest" matmul precision): about 2.5 bf16 ulps
ATTN_TOL = 2e-2
#: ``--mesh``: fp32 weights (bf16 would round the sharded partial sums
#: apart within a few tokens), so that every greedy token must agree;
#: 4 layers, so the fp32 one-chip reference fits in 16 GB
MESH_LAYERS = 4
MESH_SERVER = dict(page_size=16, num_pages=512, n_slots=4, max_len=576,
                   kernel_backend="fused", default_tier=0.5)
MESH_TRAFFIC = (8, (128, 512), 32)  # requests, prompt lengths, new tokens
#: tensor-parallel vs one-chip prefill logits, max |diff| / max |logit|,
#: fp32 at "highest" matmul precision: the sharded out- and
#: down-projections only add fp32 partial sums in another order
LOGIT_TOL = 1e-3

_compiles = {"n": 0, "s": 0.0}


def _on_duration(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles["n"] += 1
        _compiles["s"] += secs


def _check(ok: bool, what) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


def _gb(tree) -> float:
    return sum(a.nbytes for a in jax.tree.leaves(tree)) / 1e9


def _peak_gb() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


def check_attention(cfg, page: int = 16, width: int = 32,
                    batch: int = 4) -> None:
    """One fused ``paged_attn`` call per query length at ``cfg``'s
    widths against the gather-then-attend oracle: context within
    ``ATTN_TOL``, real pool pages bit-identical."""
    from repro.kernels import ops

    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rng = np.random.default_rng(0)
    for S in (1, 32):
        pos = rng.integers(0, width * page - S, size=batch).astype(np.int32)
        need = -(-(pos + S) // page)
        P = int(need.sum())
        ids = rng.permutation(P)
        bt = np.full((batch, width), -1, np.int32)
        for b, c in enumerate(np.cumsum(need) - need):
            bt[b, : need[b]] = ids[c : c + need[b]]
        keys = jax.random.split(jax.random.PRNGKey(S), 5)

        def normal(key, shape):
            return jax.random.normal(key, shape, jnp.float32).astype(
                cfg.dtype)

        args = (
            normal(keys[0], (batch, S, H, hd)),
            normal(keys[1], (batch, S, KV, hd)),
            normal(keys[2], (batch, S, KV, hd)),
            normal(keys[3], (P + 1, KV, page, hd)),
            normal(keys[4], (P + 1, KV, page, hd)),
            jnp.asarray(bt), jnp.asarray(pos), jnp.ones((batch, S), bool),
        )
        ctx, pk, pv = ops.paged_attention(*args)
        with jax.default_matmul_precision("highest"):
            ctx_r, pk_r, pv_r = ops.paged_attn_ref(*args)
        err = float(jnp.max(jnp.abs(ctx - ctx_r)))
        exact = all(bool(jnp.array_equal(a[:-1], b[:-1]))
                    for a, b in ((pk, pk_r), (pv, pv_r)))
        print(f"[attn] S={S} B={batch} context {width * page}: "
              f"max|ctx - oracle| = {err:.3e} (tol {ATTN_TOL}), "
              f"pool pages bit-identical: {exact}")
        _check(err <= ATTN_TOL and exact, (S, err, exact))


def _report(label: str, srv, requests, results, seconds: float,
            since) -> None:
    """Every request got all its tokens, in the vocabulary; print bytes,
    tokens, compiles and peak memory of one served run."""
    V = srv.cfg.vocab_size
    for rid, (_, max_new) in enumerate(requests):
        toks = results.get(rid, [])
        _check(len(toks) == max_new and all(0 <= t < V for t in toks),
               (label, rid, len(toks), max_new))
    n_tok = sum(len(v) for v in results.values())
    print(f"[{label}] bytes: params {_gb(srv.params):.3f} GB, "
          f"pools {_gb(srv.pools):.3f} GB, "
          f"FF union buffers {_gb(srv.ffn_union):.3f} GB")
    print(f"[{label}] served {len(results)} requests, {n_tok} tokens; "
          f"compiled {_compiles['n'] - since[0]} programs in "
          f"{_compiles['s'] - since[1]:.1f} s; smoke-run wall time "
          f"{seconds:.1f} s (not a benchmark); "
          f"peak_bytes_in_use {_peak_gb()}")


def serve(label: str, argv) -> dict:
    """``launch/serve.py`` once, as a user runs it."""
    from repro.launch import serve as serve_lib

    since = _compiles["n"], _compiles["s"]
    out = serve_lib.main(argv)
    _report(label, out["server"], out["requests"], out["results"],
            out["seconds"], since)
    return out


def serve_direct(label: str, cfg, params, gcfg, requests, mesh=None,
                 server=MESH_SERVER):
    """``requests`` [(prompt, max_new)] through one ``PagedServer``;
    returns (server, {rid: tokens})."""
    from repro.serving.server import PagedServer

    since = _compiles["n"], _compiles["s"]
    srv = PagedServer(cfg, params, gcfg, mesh=mesh, **server)
    for rid, (prompt, max_new) in enumerate(requests):
        srv.submit(prompt, max_new=max_new, rid=rid)
    t0 = time.perf_counter()
    results = srv.drain()
    _report(label, srv, requests, results, time.perf_counter() - t0, since)
    return srv, results


def mesh_requests(cfg, traffic=MESH_TRAFFIC):
    """``traffic`` = (requests, (lo, hi) prompt tokens, new tokens) ->
    [(prompt, max_new)] from the synthetic corpus, seed 0."""
    from repro.data.pipeline import SyntheticCorpus

    n, (lo, hi), max_new = traffic
    corpus = SyntheticCorpus(vocab=cfg.vocab_size, seed=0)
    rng = np.random.default_rng(0)
    return [(corpus.sample(int(rng.integers(lo, hi + 1)), seed=500 + rid),
             max_new) for rid in range(n)]


def prefill_logits(srv, prompt) -> np.ndarray:
    """Logits of one prefill over ``prompt`` through the server's step
    program (shard_mapped under a mesh), on fresh pools."""
    from repro.models import decoder

    cfg, page = srv.cfg, srv.pcfg.page_size
    n = -(-len(prompt) // page)
    pools = decoder.init_paged_pools(cfg, n, page, srv.kv_dtype)
    args = (jnp.arange(n, dtype=jnp.int32)[None],
            jnp.asarray(prompt, jnp.int32)[None],
            jnp.zeros((1,), jnp.int32), jnp.ones((1, len(prompt)), bool))
    if srv.tp is None:
        def step(params, pools, *a):
            return decoder.decode_step_paged(
                params, cfg, pools, *a, backend=srv.backend,
                kv_dtype=srv.kv_dtype)[0]

        logits = jax.jit(step)(srv.params, pools, *args)
    else:
        step = srv.tp.prefill(srv.tp.pool_pspecs(n, page), False, None)
        pools = srv.tp.shard_pools(pools, n, page)
        logits = step(srv.params, pools, *args, None)[0]
    return np.asarray(logits, np.float32)


def check_placement(srv, n: int) -> None:
    """Pools and the FF union buffers are split over all ``n``
    devices."""
    from repro.distributed.tp import pool_shard_bytes

    for name, tree in (("pools", srv.pools),
                       ("FF union buffers", srv.ffn_union)):
        leaves = jax.tree.leaves(tree)
        _check(leaves, name)
        for a in leaves:
            devices = {s.device for s in a.addressable_shards}
            shard = a.sharding.shard_shape(a.shape)
            _check(len(devices) == n, (name, a.shape, devices))
            _check(np.prod(shard) * n == np.prod(a.shape), (name, shard))
        print(f"[placement] {name}: {len(leaves)} arrays, each split "
              f"over {n} devices; {pool_shard_bytes(tree) / 1e9:.3f} GB "
              f"per device of {_gb(tree):.3f} GB")


def smoke_one_chip(serve_args=SERVE_ARGS, attn_cfg=None) -> None:
    from repro.configs.registry import get_config

    check_attention(attn_cfg or get_config(ARCH))
    for label, extra in (("griffin tier 0.5", ["--tier", "0.5"]),
                         ("dense", ["--no-griffin"])):
        serve(label, serve_args + extra)
        gc.collect()  # free this server's device memory before the next


def smoke_mesh(axis: str, n: int, cfg=None, server=MESH_SERVER,
               traffic=MESH_TRAFFIC) -> None:
    """The same requests served tensor-parallel over ``axis=n`` and on
    one chip with the same balanced GRIFFIN selection
    (``GriffinConfig(tp_shards=n)``): prefill logits within
    ``LOGIT_TOL``, every greedy token identical, pools and FF union buffers
    split over all ``n`` devices."""
    from repro.configs.registry import get_config
    from repro.core import GriffinConfig
    from repro.launch.mesh import make_serving_mesh
    from repro.models import decoder

    cfg = cfg or get_config(ARCH).replace(num_layers=MESH_LAYERS,
                                          dtype="float32")
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    gcfg = GriffinConfig(sparsity=0.5).replace(tp_shards=n,
                                               per_shard_topk=True)
    requests = mesh_requests(cfg, traffic)
    prompt = requests[0][0][:128]
    with jax.default_matmul_precision("highest"):
        ref, tokens_1 = serve_direct(f"one chip, tp_shards {n}", cfg,
                                     params, gcfg, requests, server=server)
        logits_1 = prefill_logits(ref, prompt)
        del ref
        gc.collect()
        out, tokens_n = serve_direct(f"mesh {axis}={n}", cfg, params, gcfg,
                                     requests, make_serving_mesh(n, axis),
                                     server)
        check_placement(out, n)
        logits_n = prefill_logits(out, prompt)
    rel = float(np.abs(logits_n - logits_1).max() / np.abs(logits_1).max())
    pairs = [(a, b) for rid, toks in tokens_1.items()
             for a, b in zip(toks, tokens_n[rid])]
    same = sum(a == b for a, b in pairs)
    first = sum(tokens_1[r][0] == tokens_n[r][0] for r in tokens_1)
    whole = sum(tokens_1[r] == tokens_n[r] for r in tokens_1)
    print(f"[compare] prefill logits over {len(prompt)} tokens: "
          f"max|diff| / max|logit| = {rel:.3e} (tol {LOGIT_TOL}); "
          f"tokens agree {same}/{len(pairs)}, first tokens "
          f"{first}/{len(tokens_1)}, requests identical "
          f"{whole}/{len(tokens_1)}")
    for rid, toks in tokens_1.items():
        if toks != tokens_n[rid]:
            at = next(i for i, (a, b) in enumerate(zip(toks, tokens_n[rid]))
                      if a != b)
            print(f"[compare] request {rid} diverges at token {at}")
    _check(rel <= LOGIT_TOL and whole == len(tokens_1), (rel, whole))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default=None, metavar="AXIS=N",
                    help="run only tensor-parallel serving over N chips "
                         "against one chip (e.g. model=4)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX finds no TPU (backend "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    dev = jax.devices()
    print(f"[device] {dev[0].platform} {dev[0].device_kind} x{len(dev)}; "
          f"compile cache {cache}")
    dtype, layers = ("fp32", MESH_LAYERS) if args.mesh else ("bf16", LAYERS)
    print(f"[config] {ARCH} at published widths, {dtype} weights from "
          f"seed 0; depth cut 48 -> {layers} layers")
    t0 = time.perf_counter()
    if args.mesh:
        from repro.launch.serve import parse_mesh

        smoke_mesh(*parse_mesh(args.mesh))
    else:
        smoke_one_chip()
    print(f"[done] smoke run {time.perf_counter() - t0:.1f} s; compiled "
          f"{_compiles['n']} programs in {_compiles['s']:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
