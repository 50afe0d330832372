"""Subprocess: 8 host devices — sharded train step + numerics parity.

Asserts the (2,4) mesh-sharded train step produces the same loss
trajectory as the single-device step (SPMD correctness end-to-end).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.distributed import sharding as shlib
from repro.launch.mesh import auto_mesh
from repro.models import decoder
from repro.training import optimizer as opt_lib
from repro.training.train_step import build_train_step, init_train_state

assert jax.device_count() == 8, jax.device_count()

cfg = get_config("yi-9b", smoke=True).replace(
    d_model=64, d_ff=128, num_heads=4, num_kv_heads=2, vocab_size=256,
    dtype="float32",
)
mesh = auto_mesh((2, 4), ("data", "model"))
rules = shlib.make_rules(phase="train", fsdp=True)
# 4-way model axis needs dims % 4 == 0: d_ff 128 ok, heads 4 ok, vocab 256 ok

opt = opt_lib.adamw(1e-2)
state = init_train_state(cfg, opt, jax.random.PRNGKey(0))
step_plain = jax.jit(build_train_step(cfg, opt))

p_specs = decoder.model_specs(cfg)
state_sh = {
    "params": shlib.tree_shardings_from_specs(p_specs, mesh, rules),
    "opt": None,
    "step": None,
}
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 256)}

def fn(state, batch):
    with shlib.axis_rules(mesh, rules):
        return build_train_step(cfg, opt)(state, batch)

state_sharded = jax.device_put(
    state,
    {
        "params": state_sh["params"],
        "opt": jax.tree.map(
            lambda _: jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            state["opt"],
        ),
        "step": jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    },
)

step_sharded = jax.jit(fn)
losses_plain, losses_sharded = [], []
s1, s2 = state, state_sharded
for _ in range(3):
    s1, m1 = step_plain(s1, batch)
    s2, m2 = step_sharded(s2, batch)
    losses_plain.append(float(m1["loss"]))
    losses_sharded.append(float(m2["loss"]))

np.testing.assert_allclose(losses_plain, losses_sharded, rtol=2e-4, atol=2e-4)
print("OK train-mesh parity", losses_plain)
