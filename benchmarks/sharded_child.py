"""Subprocess body of ``benchmarks/run.py --only sharded``.

Runs in its own process because the emulated device count must be set
before jax initializes (the parent benchmark harness keeps its single
CPU device).  Serves the *trained* tiny TP model through the paged
server single-device and shard_mapped over ``model`` axes of 2 and 4,
on one fixed trace per (spec_k, N) case, and prints a single
machine-readable JSON line the parent turns into ``BENCH_sharded.json``:

* ``token_identical`` — sharded greedy output equals single-device,
  through preemption-capable pool pressure and prefix-cache hits,
* ``pool_bytes_per_shard`` — per-device KV pool bytes, which must be
  exactly ``pool_bytes_single / N`` (the KV-head axis sharding claim),
* wall times (CPU emulation: collectives are memcpys, so these measure
  overhead, not the TPU speedup story).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"  # CPU emulation, even where a chip exists

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).parent.parent))

import jax
import numpy as np

from benchmarks.common import trained_tiny
from repro.core import GriffinConfig
from repro.distributed.tp import pool_shard_bytes
from repro.launch.mesh import make_serving_mesh
from repro.serving.server import PagedServer

# keep in sync with the literal in run.py::bench_sharded (not imported
# from here: this module's import force-sets XLA_FLAGS process-wide)
MARKER = "BENCH_SHARDED_JSON:"


def build_trace(cfg, n_req: int, rng: np.random.Generator):
    """Chat-shaped trace: a shared 32-token system prefix on most
    prompts (prefix hits) + unique tails, pool sized to force
    reclaim/preemption pressure."""
    shared = rng.integers(0, cfg.vocab_size, size=32).astype(np.int32)
    reqs = []
    for i in range(n_req):
        tail = rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(6, 18))).astype(np.int32)
        prompt = np.concatenate([shared, tail]) if i % 3 != 2 else tail
        reqs.append((prompt, int(rng.integers(8, 16))))
    return reqs


def serve(cfg, params, reqs, mesh, n_shards, spec_k):
    gcfg = GriffinConfig(sparsity=0.5, per_shard_topk=True,
                         tp_shards=n_shards)
    # 12 pages for 3 slots of up-to-12-page requests: real reclaim
    # pressure, so the identity claim spans preemption/eviction too
    srv = PagedServer(cfg, params, gcfg=gcfg, page_size=8, num_pages=12,
                      n_slots=3, prefill_chunk=16, max_len=96,
                      spec_k=spec_k, mesh=mesh)
    for i, (p, g) in enumerate(reqs):
        srv.submit(p, max_new=g, rid=i)
    t0 = time.perf_counter()
    out = srv.drain()
    wall = time.perf_counter() - t0
    return srv, out, wall


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    assert jax.device_count() == 8, jax.device_count()

    steps = 120 if args.smoke else 500
    cfg, params = trained_tiny(steps, arch="tinylm-tp")
    rng = np.random.default_rng(29)
    reqs = build_trace(cfg, 5 if args.smoke else 8, rng)

    cases = [(0, 2), (0, 4), (4, 2)] if args.smoke else \
        [(0, 2), (0, 4), (4, 2), (4, 4)]
    out_cases = []
    for spec_k, n in cases:
        s1, out1, wall1 = serve(cfg, params, reqs, None, n, spec_k)
        s2, out2, wall2 = serve(cfg, params, reqs,
                                make_serving_mesh(n), n, spec_k)
        m2 = s2.metrics.summary()
        out_cases.append({
            "spec_k": spec_k,
            "model_axis": n,
            "token_identical": out1 == out2,
            "pool_bytes_single": pool_shard_bytes(s1.pools),
            "pool_bytes_per_shard": pool_shard_bytes(s2.pools),
            "wall_single_s": wall1,
            "wall_sharded_s": wall2,
            "generated_tokens": m2["generated_tokens"],
            "tokens_per_sec_sharded": m2["tokens_per_sec"],
            "preemptions": m2["preemptions"],
            "prefix_hit_rate": m2["prefix_hit_rate"],
            "acceptance_rate": m2["acceptance_rate"],
        })
    print(MARKER, json.dumps({
        "arch": cfg.name,
        "train_steps": steps,
        "smoke": bool(args.smoke),
        "cases": out_cases,
    }))


if __name__ == "__main__":
    main()
