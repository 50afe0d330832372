"""Pallas kernel validation: sweep shapes/dtypes, assert_allclose against
the pure-jnp oracles in ref.py (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")
given = hypothesis.given

from repro.kernels import ops

hypothesis.settings.register_profile(
    "kernels", deadline=None, max_examples=12,
    suppress_health_check=[hypothesis.HealthCheck.too_slow],
)
hypothesis.settings.load_profile("kernels")

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,D,F,BK,L,n_live", [
    (1, 128, 512, 128, 1, 2),
    (4, 256, 1024, 128, 2, 4),
    (8, 128, 2048, 256, 3, 3),
    (4, 128, 512, 128, 2, 1),     # one live block
    (2, 128, 1024, 128, 1, 8),    # every block live
])
@pytest.mark.parametrize("act", ["swiglu", "geglu", "reglu"])
def test_griffin_ffn_kernel(dtype, B, D, F, BK, L, n_live, act):
    """Each row sums only its own masked neurons of the live blocks of
    its layer: rows of the buffer past the live count hold NaN and must
    not reach y."""
    rng = np.random.default_rng(B * D + F + n_live)
    x = jnp.asarray(rng.normal(size=(B, D)), dtype)

    def weights():
        w = rng.normal(size=(L, F, D)) * 0.05
        w[:, n_live * BK:] = np.nan
        return jnp.asarray(w, dtype)

    wg, w1, w2 = weights(), weights(), weights()
    mask = jnp.asarray(rng.random((B, F)) < 0.6, jnp.float32)
    layer = L - 1
    y = ops.griffin_ffn_decode(x, wg, w1, w2, mask, layer, n_live,
                               block_size=BK, activation=act)
    assert np.isfinite(np.asarray(y)).all()
    y_ref = ops.griffin_ffn_ref(x, wg, w1, w2, mask, layer, n_live, BK,
                                activation=act)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **_tol(dtype))


@given(
    s=st.integers(1, 300),
    f=st.sampled_from([128, 384, 1024]),
    dt=st.sampled_from(DTYPES),
    seed=st.integers(0, 1000),
)
def test_expert_stat_kernel(s, f, dt, seed):
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.normal(size=(s, f)), dt)
    got = ops.griffin_stat(z)
    ref = ops.expert_stat_ref(z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-2,
                               atol=2e-2)


def test_expert_stat_batched():
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=(3, 70, 256)), jnp.float32)
    got = ops.griffin_stat(z)
    ref = jax.vmap(ops.expert_stat_ref)(z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("S,D,F", [(64, 128, 512), (300, 256, 1024)])
def test_glu_ffn_kernel(dtype, S, D, F):
    rng = np.random.default_rng(S + D)
    x = jnp.asarray(rng.normal(size=(S, D)), dtype)
    wg = jnp.asarray(rng.normal(size=(D, F)) * 0.05, dtype)
    w1 = jnp.asarray(rng.normal(size=(D, F)) * 0.05, dtype)
    w2 = jnp.asarray(rng.normal(size=(F, D)) * 0.05, dtype)
    got = ops.glu_ffn_forward(x, wg, w1, w2)
    ref = ops.glu_ffn_ref(x, wg, w1, w2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **_tol(dtype))


def test_griffin_kernel_matches_model_ffn():
    """Kernel path == the model's compact()+ffn_forward path: a buffer
    holding the selected neurons' rows (padded past them), one mask row
    per decode row."""
    from repro.configs.registry import get_config
    from repro.core import GriffinConfig, select_experts
    from repro.models.layers import ffn as ffn_lib

    cfg = get_config("tinylm")
    key = jax.random.PRNGKey(3)
    d, f, bk = 64, 512, 128
    p = {
        "w1": jax.random.normal(key, (d, f)) * 0.1,
        "wg": jax.random.normal(jax.random.fold_in(key, 1), (d, f)) * 0.1,
        "w2": jax.random.normal(jax.random.fold_in(key, 2), (f, d)) * 0.1,
    }
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, 7, d))
    _, stats = ffn_lib.ffn_forward(p, x, cfg, collect_stats=True)
    idx = select_experts(stats["s_sq"], GriffinConfig(sparsity=0.6))
    k = int(idx.shape[0])
    y_model, _ = ffn_lib.ffn_forward(ffn_lib.compact_ffn_params(p, idx), x, cfg)
    buf_idx = jnp.concatenate([idx, jnp.full((f - k,), idx[-1])])
    rows = lambda w: jnp.take(w, buf_idx, axis=0)[None]
    mask = jnp.broadcast_to(jnp.arange(f) < k, (2, f))
    xq = x[:, -1]  # decode: one token
    y_kernel = ops.griffin_ffn_decode(
        xq, rows(p["wg"].T), rows(p["w1"].T), rows(p["w2"]), mask, 0,
        -(-k // bk), block_size=bk,
    )
    np.testing.assert_allclose(
        np.asarray(y_kernel), np.asarray(y_model[:, -1]), rtol=1e-4, atol=1e-4
    )
