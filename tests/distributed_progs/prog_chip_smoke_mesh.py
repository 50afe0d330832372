"""Subprocess: 4 host devices — ``chip_smoke.py --mesh model=4``'s path
at a tiny size.

The tensor-parallel server over a ``model=4`` mesh against one device
with the same balanced selection (``GriffinConfig(tp_shards=4)``):
prefill logits within ``chip_smoke.LOGIT_TOL``, every request served in
full with identical tokens, pools and slot buffers split over all four
devices.
"""
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402

import chip_smoke  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402

assert jax.device_count() == 4, jax.device_count()

# untied head, as yi-9b's: with tied embeddings a random tiny model
# echoes its input token, and a wrong sharded sum would not change it
chip_smoke.smoke_mesh(
    "model", 4, cfg=get_config("tinylm-tp").replace(tie_embeddings=False),
    server=dict(page_size=8, num_pages=32, n_slots=2, max_len=64,
                kernel_backend="fused", default_tier=0.5),
    traffic=(3, (20, 40), 5),
)
print("OK chip smoke mesh path")
