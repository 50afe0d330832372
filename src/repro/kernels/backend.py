"""Shared Pallas backend selection.

Every kernel wrapper in this package takes ``interpret: Optional[bool]``
and resolves ``None`` through :func:`default_interpret` — compile to
Mosaic on a real TPU backend, fall back to the Pallas interpreter
everywhere else (CPU tests validate the kernels against ``ref.py``
oracles in interpret mode; ``tests/test_tpu_compile.py`` compiles them
for a described v5e).

Centralizing the choice here means no kernel can silently ship with a
hardcoded ``interpret=True`` that would de-optimize real TPU runs — the
bug this module replaced (``griffin_ffn``/``paged_gather``/
``expert_stat`` each used to default to interpret unconditionally).
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Interpret off-TPU; compile for real on TPU."""
    return not on_tpu()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> backend default; explicit bools pass through."""
    return default_interpret() if interpret is None else bool(interpret)
