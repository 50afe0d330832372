"""Logical-axis sharding rules (MaxText-style) and activation constraints.

A *rule set* maps logical axis names (see ``repro.models.param``) to mesh
axis names (or tuples of them, or None).  Layers call
``constrain(x, ("batch", "seq", "embed"))`` at strategic points; when no
rules/mesh are active (unit tests, single-device runs) this is a no-op.

Resolution is **divisibility-aware**: a mesh axis that does not evenly
divide the corresponding dimension is dropped (replicated) rather than
erroring — e.g. smollm's 15 query heads on a 16-way ``model`` axis, or a
``batch=1`` long-context decode on a 16-way ``data`` axis.  Each
distinct drop emits a one-time warning: a silently replicated weight is
an N× memory regression that otherwise only shows up in an OOM (the
GRIFFIN-compacted FF width is the canonical trap — halving ``d_ff``
can turn a dividing ``model`` axis into a non-dividing one, see
``repro.core.griffin.GriffinConfig.k_of`` for the divisible-``k_ff``
rule that prevents it).

This module also hosts the **shard_map tensor-parallel hooks** for the
paged serving path (DESIGN.md section 11): inside a
``with tp_axis("model")`` scope (entered by the per-shard step functions
in ``repro.distributed.tp`` while shard_map traces them),
``psum_if_tp`` becomes a cross-shard ``lax.psum`` — the layers call it
after every contraction over a sharded axis (attention out-projection,
FFN down-projection, GRIFFIN row norms).  Outside the scope it is the
identity, so the single-device path and the GSPMD training path (which
inserts its own collectives) are untouched.
"""
from __future__ import annotations

import contextlib
import threading
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.param import ParamSpec, tree_map_specs

MeshAxes = Union[None, str, Tuple[str, ...]]
Rules = Dict[str, MeshAxes]

_state = threading.local()


def _current() -> Tuple[Optional[Mesh], Optional[Rules]]:
    return getattr(_state, "mesh", None), getattr(_state, "rules", None)


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Rules):
    """Activate (mesh, rules) for logical constraints inside a jit trace."""
    prev = _current()
    _state.mesh, _state.rules = mesh, rules
    try:
        yield
    finally:
        _state.mesh, _state.rules = prev


def active_rules() -> Tuple[Optional[Mesh], Optional[Rules]]:
    return _current()


# ---------------------------------------------------------------------------
# shard_map tensor-parallel hooks (paged serving; repro.distributed.tp)
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def tp_axis(name: str):
    """Mark the enclosed trace as running *inside* a shard_map shard
    whose tensor-parallel mesh axis is ``name``: ``psum_if_tp`` becomes
    a real ``lax.psum`` over that axis."""
    prev = getattr(_state, "tp_axis", None)
    _state.tp_axis = name
    try:
        yield
    finally:
        _state.tp_axis = prev


def tp_axis_name() -> Optional[str]:
    return getattr(_state, "tp_axis", None)


def psum_if_tp(x: jax.Array) -> jax.Array:
    """Cross-shard all-reduce under an active ``tp_axis``, else identity.

    Layers call this on every partial sum produced by contracting over
    a model-sharded axis (attention heads in the out-projection, FF
    hidden neurons in the down-projection, the GRIFFIN per-token row
    norm).  The hook keeps the layer code single-source: the same
    function body is the single-device program, the GSPMD program
    (context inactive — GSPMD inserts its own collectives), and the
    shard_map per-shard program."""
    name = tp_axis_name()
    return jax.lax.psum(x, name) if name is not None else x


def mesh_axis_size(mesh, name: str) -> int:
    """Size of one mesh axis (works for Mesh and AbstractMesh)."""
    return dict(mesh.shape)[name]


_mesh_size = mesh_axis_size  # internal alias

# Logical axes where a divisibility drop is routine and replication is
# the *intended* layout (transient activations, host-scheduler state) —
# e.g. a batch=1 decode on a 16-way data axis.  Warning there would
# train operators to ignore the case the warning exists for: a
# persistent WEIGHT silently replicated N× (the compacted-FF trap).
_QUIET_DROP_AXES = frozenset(
    {"batch", "seq", "act_embed", "kv_seq", "window", "cap",
     "pages", "page", "layers"}
)

# one-time divisibility-drop warnings: keyed by (logical axis, mesh
# axis, residual dim, mesh size) so each distinct drop is reported once
# per process, not once per trace
_div_warned: set = set()


def _warn_divisibility_drop(ax: Optional[str], mesh_name: str, dim: int,
                            rem: int, size: int) -> None:
    if ax in _QUIET_DROP_AXES:
        return
    key = (ax, mesh_name, rem, size)
    if key in _div_warned:
        return
    _div_warned.add(key)
    # rem is what this axis actually failed to divide (earlier mesh
    # axes of a tuple rule already divided dim down to rem)
    what = f"dimension {dim}" if rem == dim else \
        f"dimension {dim} (residual {rem} after earlier mesh axes)"
    msg = (
        f"sharding: dropping mesh axis {mesh_name!r} (size {size}) for "
        f"logical axis {ax!r}: {what} is not divisible — the tensor is "
        f"REPLICATED over {mesh_name!r} ({size}x the memory of the "
        f"sharded layout)."
    )
    if ax == "mlp":
        msg += (
            " For GRIFFIN-compacted FF weights, pad the selection to a "
            "divisible k_ff (GriffinConfig(tp_shards=N) rounds k up "
            "automatically)."
        )
    warnings.warn(msg, stacklevel=3)


def spec_for(
    axes: Sequence[Optional[str]],
    rules: Rules,
    mesh: Mesh,
    dims: Optional[Sequence[int]] = None,
) -> P:
    """Resolve logical axes to a PartitionSpec under ``rules``.

    * A mesh axis may appear only once in the spec (GSPMD requirement);
      later conflicting occurrences are replicated.
    * If ``dims`` is given, mesh axes whose size does not divide the
      dimension are dropped — with a one-time warning per distinct
      (logical axis, mesh axis, dim, size), because the resulting
      replication silently costs mesh-size× the memory.
    """
    used: set = set()
    out = []
    for i, ax in enumerate(axes):
        m = rules.get(ax) if ax is not None else None
        if m is None:
            out.append(None)
            continue
        names = (m,) if isinstance(m, str) else tuple(m)
        names = tuple(n for n in names if n not in used and n in mesh.axis_names)
        if dims is not None:
            kept = []
            rem = dims[i]
            for n in names:
                sz = _mesh_size(mesh, n)
                if rem % sz == 0:
                    kept.append(n)
                    rem //= sz
                else:
                    _warn_divisibility_drop(ax, n, dims[i], rem, sz)
            names = tuple(kept)
        if not names:
            out.append(None)
            continue
        used.update(names)
        out.append(names[0] if len(names) == 1 else names)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def sharding_for(
    axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: Rules,
    dims: Optional[Sequence[int]] = None,
) -> NamedSharding:
    return NamedSharding(mesh, spec_for(axes, rules, mesh, dims))


def tree_shardings_from_specs(spec_tree: Any, mesh: Mesh, rules: Rules) -> Any:
    """Map a ParamSpec tree to a NamedSharding tree (divisibility-aware)."""
    return tree_map_specs(
        lambda s: sharding_for(s.axes, mesh, rules, s.shape), spec_tree
    )


def constrain(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Apply a logical sharding constraint if rules are active, else no-op."""
    mesh, rules = _current()
    if mesh is None or rules is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"axes {axes} rank != array rank {x.ndim} ({x.shape})")
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_for(axes, rules, mesh, x.shape))
    )


# ---------------------------------------------------------------------------
# Rule policies
# ---------------------------------------------------------------------------

def make_rules(
    *,
    phase: str,  # "train" | "serve"
    fsdp: bool = False,
    seq_parallel: bool = False,
    expert_2d: bool = False,
    kv_seq_model: bool = False,
    head_dim_fallback: bool = False,
) -> Rules:
    """Build a logical->mesh rule set.

    fsdp:         shard the ``embed`` axis of weights over ``data``
                  (ZeRO-3-ish; weights gathered per layer by GSPMD).
    seq_parallel: shard boundary activations' ``seq`` over ``model``
                  (sequence parallelism; GSPMD inserts AG/RS pairs).
    expert_2d:    shard ``experts`` over (data, model)
                  (deepseek: 256 experts == 16x16 mesh exactly).
    kv_seq_model: additionally shard decode KV caches' sequence axis over
                  ``model`` (flash-decode-style partial softmax) — used
                  when the arch's kv_heads cannot occupy the model axis,
                  so cache reads stay sharded instead of being gathered.
    """
    rules: Rules = {
        "batch": ("pod", "data"),
        "seq": ("model",) if seq_parallel else None,
        "act_embed": None,
        "embed": "data" if fsdp else None,
        "mlp": "model",
        "heads": "model",
        "kv_heads": "model",
        # decode-only TP fallback: when an arch's (kv-)head count can't
        # occupy the 16-way model axis (llava 56H/8KV), shard head_dim
        # instead — weights stay distributed, scores psums are tiny at
        # decode. (Divisibility-aware resolution: heads win when they fit.)
        "head_dim": "model" if head_dim_fallback else None,
        "vocab": "model",
        "tok_vocab": None,  # untied embedding table rows: replicate
        "lora": None,
        "experts": ("data", "model") if expert_2d else "model",
        "ssm_inner": "model",
        "ssm_heads": "model",
        "ssm_state": None,
        "lru": "model",
        "conv": None,
        "layers": None,
        # decode KV cache sequence axis: context parallelism over data
        # (and model, when kv-heads can't use it)
        "kv_seq": ("data", "model") if kv_seq_model else "data",
        "cap": None,
        "window": ("data", "model") if kv_seq_model else "data",
    }
    return rules


def make_paged_tp_rules(axis: str = "model") -> Rules:
    """Logical->mesh rules for shard_map tensor-parallel *paged serving*
    (DESIGN.md section 11).

    Head-parallel attention + FF-hidden-parallel FFN on one mesh axis:
    ``heads``/``kv_heads`` shard the projections and the KV page pools,
    ``mlp`` shards the FF hidden axis (including the GRIFFIN union
    buffer, one part per shard).  Everything the host mutates or the
    shards must agree on — block tables, positions, pages, the embed
    table and LM head — stays replicated, so logits come out replicated
    and the scheduler needs no device-aware logic.
    """
    return {
        "batch": None,
        "seq": None,
        "act_embed": None,
        "embed": None,
        "heads": axis,
        "kv_heads": axis,
        "head_dim": None,
        "mlp": axis,
        "vocab": None,
        "tok_vocab": None,
        "pages": None,
        "page": None,
        "layers": None,
    }


def describe(rules: Rules) -> str:
    return ", ".join(f"{k}->{v}" for k, v in rules.items() if v)
