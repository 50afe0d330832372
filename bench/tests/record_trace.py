"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

On one chip:  python3 bench/tests/record_trace.py <out.xplane.pb>

Inside a ``bench_window`` annotation: a jitted matmul, a 50 ms host
sleep, the fused paged-attention kernel once, then the matmul again.
The device is therefore idle for at least 50 ms of the window, in one
gap, and the kernel runs once.
"""
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

SLEEP_S = 0.05


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    from repro.kernels import ops

    mm = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    B, S, H, KV, hd, page, W = 4, 1, 32, 4, 128, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    kn = jax.random.normal(ks[1], (B, S, KV, hd), jnp.bfloat16)
    vn = jax.random.normal(ks[2], (B, S, KV, hd), jnp.bfloat16)
    pk = jax.random.normal(ks[3], (B * W + 1, KV, page, hd), jnp.bfloat16)
    pv = jax.random.normal(ks[4], (B * W + 1, KV, page, hd), jnp.bfloat16)
    bt = jnp.arange(B * W, dtype=jnp.int32).reshape(B, W)
    pos = jnp.full((B,), W * page - 1, jnp.int32)
    mask = jnp.ones((B, S), bool)
    attn = jax.jit(ops.paged_attention)
    jax.block_until_ready((mm(x), attn(q, kn, vn, pk, pv, bt, pos, mask)))
    tmp = ROOT / ".bench_cache" / "record_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp))
    with jax.profiler.TraceAnnotation("bench_window"):
        mm(x).block_until_ready()
        time.sleep(SLEEP_S)
        jax.block_until_ready(attn(q, kn, vn, pk, pv, bt, pos, mask))
        mm(x).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(tmp.rglob("*.xplane.pb"))[-1]
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"record_trace: {out} ({Path(out).stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
