"""Subprocess: elastic restart — train on a (4,2) mesh, checkpoint,
"lose" 2 data rows, reshard onto a (2,2) mesh, continue training.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpointer
from repro.configs.registry import get_config
from repro.distributed import sharding as shlib
from repro.models import decoder
from repro.launch.mesh import auto_mesh
from repro.runtime.elastic import build_mesh, plan_remesh, reshard
from repro.training import optimizer as opt_lib
from repro.training.train_step import build_train_step, init_train_state

assert jax.device_count() == 8

cfg = get_config("tinylm").replace(
    num_layers=2, d_model=32, d_ff=64, num_heads=2, num_kv_heads=2,
    head_dim=16, vocab_size=256,
)
opt = opt_lib.adamw(1e-2)
rules = shlib.make_rules(phase="train", fsdp=False)

mesh1 = auto_mesh((4, 2), ("data", "model"))
state = init_train_state(cfg, opt, jax.random.PRNGKey(0))

def make_step(mesh):
    def fn(state, batch):
        with shlib.axis_rules(mesh, rules):
            return build_train_step(cfg, opt)(state, batch)
    return jax.jit(fn)

batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256)}
step1 = make_step(mesh1)
state, m = step1(state, batch)
loss_before = float(m["loss"])

with tempfile.TemporaryDirectory() as d:
    checkpointer.save(d, 1, state)

    # two data rows fail -> shrink to (2, 2)
    plan = plan_remesh((4, 2), ("data", "model"), failed_data_rows=[1, 3])
    assert plan.new_shape == (2, 2)
    mesh2 = build_mesh(plan)
    restored, step_n = checkpointer.restore(d)
    p_specs = decoder.model_specs(cfg)
    restored["params"] = reshard(restored["params"], p_specs, mesh2, rules)

    # scale batch by the plan's factor (keep per-replica batch fixed)
    nb = int(8 * plan.global_batch_scale)
    batch2 = {"tokens": batch["tokens"][:nb]}
    step2 = make_step(mesh2)
    state2, m2 = step2(restored, batch2)
    assert np.isfinite(float(m2["loss"]))

print("OK elastic remesh", loss_before, float(m2["loss"]))
