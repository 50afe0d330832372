"""GRIFFIN — Gating by Repetition In Feedforward Intermediate Neurons.

The paper's algorithm (section 4.2), as a composable JAX module:

1. **Prompt phase**: the model runs its full FF blocks and emits, per FF
   layer, the per-sample squared statistic ``s_sq[b, j] = sum_t
   z[b,t,j]^2 / ||z[b,t]||^2`` (eq. 6 squared; computed streaming inside
   the layers, see ``repro.models.layers.ffn.griffin_stat_sq``).
2. **Selection**: ``select_experts`` reduces ``s_sq`` to a single expert
   index set per layer.  Batch aggregation follows eq. 7:
   ``s-bar = sum_i s_i / sqrt(S_i)``.  Selection strategies live in
   ``repro.core.selector`` (top-k default; sampling ablations;
   TPU block-aligned mode).
3. **Generation phase**: ``compact`` gathers rows/columns of the FF
   weights (the paper's reparameterization) so every decode step runs
   dense ``[k, D]`` matmuls.

Distributed note (DESIGN.md section 3): under tensor parallelism the
statistic arrives shard-local; with ``per_shard_topk`` the top-(k/TP)
selection is computed inside each shard (collective-free, balanced).
This is realized by reshaping the statistic to ``[TP, F/TP]`` and
selecting per row — identical math on one host, shard-local under pjit.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import selector as selector_lib

# Single-sourced default for balanced shard-local selection (DESIGN.md
# section 3).  Call sites that construct a GriffinConfig should omit
# ``per_shard_topk`` and inherit this; with ``tp_shards == 1`` the flag
# is inert (selection falls through to plain top-k), and under a mesh
# the server forces it on, so the default is safe everywhere.
DEFAULT_PER_SHARD_TOPK = True

# The serving tiers (DESIGN.md section 16): the fraction of FF experts a
# request KEEPS.  1.0 is the dense path (no compaction at all); the rest
# scale each layer's expert budget through the SparsityProfile.
TIERS = (0.25, 0.5, 0.75, 1.0)


def resolve_tier(tier) -> Optional[float]:
    """Validate a request tier. None means "no tier" (legacy global
    ``gcfg.k_of`` selection); otherwise the value must be one of TIERS."""
    if tier is None:
        return None
    try:
        t = float(tier)
    except (TypeError, ValueError):
        raise ValueError(f"tier must be a number in {TIERS}, got {tier!r}")
    for cand in TIERS:
        if abs(t - cand) < 1e-9:
            return cand
    raise ValueError(f"unknown sparsity tier {tier!r}; valid tiers: {TIERS}")


def tier_k(d_ff: int, tier: float, weight: float = 1.0,
           tp_shards: int = 1) -> int:
    """Expert count for one layer at a tier: ``round(d_ff * tier * w)``,
    clamped to [1, d_ff] and rounded up to a ``tp_shards`` multiple (the
    same divisible-``k_ff`` rule as ``GriffinConfig.k_of``, applied per
    layer)."""
    k = int(round(d_ff * float(tier) * float(weight)))
    k = max(1, min(d_ff, k))
    if tp_shards > 1:
        k = min(d_ff, -(-k // tp_shards) * tp_shards)
    return k


@dataclass(frozen=True)
class GriffinConfig:
    sparsity: float = 0.5          # fraction of FF neurons REMOVED
    mode: str = "topk"             # topk | sampling | topk_sampling | blocks
    block_size: int = 128          # for mode="blocks" (TPU-aligned)
    per_shard_topk: bool = DEFAULT_PER_SHARD_TOPK  # balanced TP selection
    tp_shards: int = 1             # logical shard count for balanced top-k
    seed: int = 0                  # for sampling modes

    def k_of(self, d_ff: int) -> int:
        """Expert count for an FF width of ``d_ff``.

        With ``tp_shards > 1`` the count is rounded **up** to a multiple
        of the shard count: under tensor parallelism the compacted FF
        hidden axis must stay divisible by the ``model`` mesh axis, or
        the sharding rules silently replicate the compacted weights
        (``distributed.sharding.spec_for`` drops non-dividing axes —
        an N× memory blow-up with no error).  Padding the selection by
        at most ``tp_shards - 1`` extra experts costs a sliver of the
        sparsity win and keeps every shard's pruned width identical.
        """
        k = int(round(d_ff * (1.0 - self.sparsity)))
        k = max(1, min(d_ff, k))
        if self.tp_shards > 1:
            k = min(d_ff, -(-k // self.tp_shards) * self.tp_shards)
        return k

    def replace(self, **kw) -> "GriffinConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class SparsityProfile:
    """Per-layer expert-budget weights (DESIGN.md section 16).

    ``weights`` maps FF-layer paths (``"seg{i}/{name}"``, the same keys
    as ``models.decoder.extract_ffn_tree``) to per-instance multipliers:
    a scan-stacked layer with ``n`` instances carries ``n`` weights, an
    unrolled layer one.  A layer at tier ``t`` keeps ``tier_k(F, t, w)``
    experts — weight 1.0 everywhere is the uniform profile and
    reproduces the global ``round(F * t)`` budget exactly.  Profiles are
    derived offline from flocking statistics (``analysis/profile.py``)
    and loaded by the server; a missing path defaults to weight 1.0.
    """
    weights: Tuple[Tuple[str, Tuple[float, ...]], ...] = ()
    arch: str = ""
    note: str = ""

    def __post_init__(self):
        for path, ws in self.weights:
            for w in ws:
                if not (w > 0.0):
                    raise ValueError(
                        f"profile weight for {path!r} must be > 0, got {w}")

    def weight_map(self) -> Dict[str, Tuple[float, ...]]:
        return dict(self.weights)

    def weights_for(self, path: str, n: int) -> Tuple[float, ...]:
        ws = self.weight_map().get(path)
        if ws is None:
            return (1.0,) * n
        if len(ws) != n:
            raise ValueError(
                f"profile for {path!r} carries {len(ws)} weights but the "
                f"layer has {n} instances"
            )
        return tuple(float(w) for w in ws)

    @classmethod
    def uniform(cls, arch: str = "") -> "SparsityProfile":
        """Weight 1.0 for every layer: per-layer budgets degenerate to
        the global ``round(F * tier)`` rule."""
        return cls(weights=(), arch=arch, note="uniform")

    def to_json(self) -> str:
        return json.dumps(
            {
                "arch": self.arch,
                "note": self.note,
                "weights": {p: list(ws) for p, ws in self.weights},
            },
            indent=2, sort_keys=True,
        )

    @classmethod
    def from_json(cls, text: str) -> "SparsityProfile":
        d = json.loads(text)
        return cls(
            weights=tuple(sorted(
                (str(p), tuple(float(w) for w in ws))
                for p, ws in d.get("weights", {}).items()
            )),
            arch=str(d.get("arch", "")),
            note=str(d.get("note", "")),
        )

    def save(self, path) -> None:
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    @classmethod
    def load(cls, path) -> "SparsityProfile":
        with open(path) as f:
            return cls.from_json(f.read())


def aggregate_stats(s_sq: jax.Array, seq_lens: Optional[jax.Array] = None) -> jax.Array:
    """Eq. 7: s-bar = sum_i s_i / sqrt(S_i) over the batch axis.

    s_sq: [B, F] per-sample *squared* statistics; returns [F].
    Note ``||[Z-bar]_{.,j}||_2 <= sqrt(S)``, so s_i/sqrt(S_i) weights each
    sample's statistic to a comparable scale regardless of prompt length.
    """
    s = jnp.sqrt(jnp.maximum(s_sq.astype(jnp.float32), 0.0))
    if seq_lens is not None:
        s = s / jnp.sqrt(seq_lens.astype(jnp.float32))[:, None]
    return jnp.sum(s, axis=0)


def select_experts(
    s_sq: jax.Array,
    gcfg: GriffinConfig,
    d_ff: Optional[int] = None,
    seq_lens: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
    k: Optional[int] = None,
) -> jax.Array:
    """Reduce statistics to a sorted expert index set.

    s_sq: [B, F] (batch aggregated via eq. 7) or [F].
    ``k`` overrides the global ``gcfg.k_of(F)`` budget — the per-layer
    profile/tier path (``plan_k_tree``) supplies it per layer.
    Returns idx: [k] int32, sorted ascending (gather-friendly).
    """
    s = (
        aggregate_stats(s_sq, seq_lens)
        if s_sq.ndim == 2
        else jnp.sqrt(jnp.maximum(s_sq.astype(jnp.float32), 0.0))
    )
    F = d_ff or s.shape[-1]
    k = gcfg.k_of(F) if k is None else int(k)
    if gcfg.mode == "blocks":
        return selector_lib.select_blocks(s, k, gcfg.block_size)
    if gcfg.mode == "sampling":
        return selector_lib.select_sampling(s, k, rng)
    if gcfg.mode == "topk_sampling":
        return selector_lib.select_topk_sampling(s, k, rng)
    if gcfg.per_shard_topk and gcfg.tp_shards > 1 and F % gcfg.tp_shards == 0 \
            and k % gcfg.tp_shards == 0:
        return selector_lib.select_topk_per_shard(s, k, gcfg.tp_shards)
    return selector_lib.select_topk(s, k)


def compact(ffn_params: Dict, idx: jax.Array, shards: int = 1) -> Dict:
    """Paper reparameterization: gather the expert neurons' weights."""
    from repro.models.layers.ffn import compact_ffn_params

    return compact_ffn_params(ffn_params, idx, shards=shards)


# ---------------------------------------------------------------------------
# Whole-model helpers: the per-layer statistic trees produced by prefill
# mirror the segment structure of the model params (see models/decoder.py).
# ---------------------------------------------------------------------------

def select_tree(
    stats_tree: Any,
    gcfg: GriffinConfig,
    seq_lens: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
) -> Any:
    """Map selection over a tree of stacked stats.

    Leaves are stats dicts whose ``s_sq`` entries are [B, F] (single
    layer) or [n, B, F] (scan-stacked); returns [k] / [n, k] indices.
    """

    def one(leaf) -> jax.Array:
        s_sq = leaf["s_sq"] if isinstance(leaf, dict) else leaf
        if s_sq.ndim == 3:  # [n, B, F] scan-stacked
            return jax.vmap(lambda s: select_experts(s, gcfg, seq_lens=seq_lens,
                                                     rng=rng))(s_sq)
        return select_experts(s_sq, gcfg, seq_lens=seq_lens, rng=rng)

    return jax.tree.map(
        one, stats_tree,
        is_leaf=lambda x: isinstance(x, dict) and "s_sq" in x,
    )


def compact_tree(ffn_params_tree: Any, idx_tree: Any, shards: int = 1) -> Any:
    """Compact every FF block in a (possibly scan-stacked) params tree.

    ``ffn_params_tree``/``idx_tree`` leaves are dicts of stacked weights
    [n, D, F] etc. paired with idx [n, k]; vmapped gather per layer.
    ``shards``: TP degree for shard-local gathers (per-shard selection).
    """

    def one(ffn_params: Dict, idx: jax.Array) -> Dict:
        fn = lambda p, i: compact(p, i, shards=shards)
        if idx.ndim == 2:  # scan-stacked
            return jax.vmap(fn)(ffn_params, idx)
        return fn(ffn_params, idx)

    # tree of dicts: map at the dict level using idx tree structure
    return jax.tree.map(
        one,
        ffn_params_tree,
        idx_tree,
        is_leaf=lambda x: isinstance(x, dict) and ("w1" in x or "w2" in x),
    )


# ---------------------------------------------------------------------------
# Per-layer profiles + tiers (DESIGN.md section 16): per-layer budgets
# and the selection every serving path goes through.
# ---------------------------------------------------------------------------

def ffn_widths(cfg) -> Dict[str, Tuple[int, int]]:
    """``{"seg{i}/{name}": (n_instances, d_ff)}`` for every
    GRIFFIN-prunable FF block (mirrors ``decoder.extract_ffn_tree``)."""
    from repro.models import decoder

    out: Dict[str, Tuple[int, int]] = {}
    for i, seg in enumerate(decoder.build_plan(cfg)):
        for j, desc in enumerate(seg.descs):
            name = f"pos{j}" if seg.kind == "scan" else f"layer{j}"
            if desc.ffn == "dense":
                F = cfg.d_ff
            elif desc.ffn == "moe" and cfg.num_shared_experts:
                F = cfg.moe_d_ff * cfg.num_shared_experts
            else:
                continue
            n = seg.n if seg.kind == "scan" else 1
            out[f"seg{i}/{name}"] = (n, F)
    return out


def plan_k_tree(
    cfg,
    gcfg: GriffinConfig,
    tier: Optional[float] = None,
    profile: Optional[SparsityProfile] = None,
) -> Dict[str, Tuple[int, ...]]:
    """Per-layer expert budgets: ``{"seg{i}/{name}": (k per instance,)}``.

    ``tier is None`` is the legacy path — every layer gets the global
    ``gcfg.k_of(F)``.  With a tier, each instance keeps
    ``tier_k(F, tier, profile_weight, tp_shards)`` experts.  Counts are
    the widths the selector actually returns (``selected_width`` rounds
    block-mode budgets to whole blocks), so they are usable directly for
    buffer sizing and tick bucketing.
    """
    out: Dict[str, Tuple[int, ...]] = {}
    for path, (n, F) in ffn_widths(cfg).items():
        if tier is None:
            ks = (gcfg.k_of(F),) * n
        else:
            ws = (profile or SparsityProfile.uniform()).weights_for(path, n)
            ks = tuple(tier_k(F, tier, w, gcfg.tp_shards) for w in ws)
        out[path] = tuple(
            selector_lib.selected_width(gcfg.mode, k, F, gcfg.block_size)
            for k in ks
        )
    return out


def compaction_shards(gcfg: GriffinConfig, k: int, d_ff: int) -> int:
    """TP degree for the shard-local compaction gather.

    The shard-local ``take_along_axis`` layout is only valid when the
    selection itself was per-shard balanced — plain top-k under
    ``per_shard_topk`` with divisible widths.  Every other mode
    (sampling, blocks) places experts arbitrarily across shards, where
    the shard-local gather silently picks wrong rows; those fall back to
    the plain (order-preserving) gather, which is correct under TP
    regardless of placement because the compacted FF psums over the full
    expert axis.
    """
    sh = gcfg.tp_shards
    if (
        sh > 1
        and gcfg.per_shard_topk
        and gcfg.mode == "topk"
        and d_ff % sh == 0
        and k % sh == 0
    ):
        return sh
    return 1


def _mask_dead_rows(pruned: Dict, keep: jax.Array) -> Dict:
    """Zero the ``w2`` rows of padded (dead) experts: every other leaf of
    a dead expert may hold arbitrary gathered values — only the ``w2``
    row decides its contribution, and a zero row contributes exactly
    ``0.0`` to the decode matmul."""
    out = dict(pruned)
    out["w2"] = jnp.where(keep[:, None], pruned["w2"],
                          jnp.zeros_like(pruned["w2"]))
    return out


def select_per_layer(
    stats_tree: Any,
    gcfg: GriffinConfig,
    *,
    ks: Optional[Dict[str, Tuple[int, ...]]] = None,
    seq_lens: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
) -> Dict[str, List[jax.Array]]:
    """Expert selection of every FF layer: ``{"seg{i}/{name}": [idx per
    instance]}`` — one sorted index array per scan instance (one for an
    unrolled layer), each of its own budget.  ``ks`` comes from
    ``plan_k_tree`` (None → the global ``gcfg.k_of`` budget everywhere).
    Selection runs as a static Python loop over instances (trace-safe:
    per-instance ``k`` stays a Python int under jit)."""
    out: Dict[str, List[jax.Array]] = {}
    for seg, layers in stats_tree.items():
        for name, leaf in layers.items():
            path = f"{seg}/{name}"
            s_sq = leaf["s_sq"] if isinstance(leaf, dict) else leaf
            scan = s_sq.ndim == 3
            n = s_sq.shape[0] if scan else 1
            k_list = tuple(ks[path]) if ks is not None else (None,) * n
            out[path] = [
                select_experts(s_sq[i] if scan else s_sq, gcfg,
                               seq_lens=seq_lens, rng=rng, k=k_list[i])
                for i in range(n)
            ]
    return out


def select_and_compact(
    stats_tree: Any,
    ffn_tree: Any,
    gcfg: GriffinConfig,
    *,
    ks: Optional[Dict[str, Tuple[int, ...]]] = None,
    seq_lens: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
    on_select=None,
) -> Tuple[Any, Dict[str, int]]:
    """Selection + compaction with per-layer expert budgets — the entry
    point of the contiguous engines (``serving/engine.py``, the fused
    prefill step); the paged server selects only
    (``select_per_layer``) and decodes over a union buffer.

    ``stats_tree``/``ffn_tree`` are the pruned-stats and FF-params trees
    (``decoder.prune_stats_tree`` / ``decoder.extract_ffn_tree``
    structure); ``ks`` comes from ``plan_k_tree`` (None → the legacy
    global ``gcfg.k_of`` budget everywhere, bit-identical to
    ``select_tree`` + ``compact_tree``).  Within a scan-stacked leaf,
    instances with different budgets are padded to the leaf's widest
    selection with dead (zero-``w2``-row) experts, so the stacked buffer
    keeps one static shape.

    ``on_select(path, idx_list)`` observes the raw (unpadded)
    per-instance selections (flocking telemetry).
    Returns ``(pruned_tree, widths)`` with ``widths[path]`` = the leaf's
    buffer width.
    """
    out: Dict[str, Any] = {}
    widths: Dict[str, int] = {}
    sel_tree = select_per_layer(stats_tree, gcfg, ks=ks, seq_lens=seq_lens,
                                rng=rng)
    for path, sels in sel_tree.items():
        seg, name = path.split("/")
        leaf = stats_tree[seg][name]
        s_sq = leaf["s_sq"] if isinstance(leaf, dict) else leaf
        scan, F = s_sq.ndim == 3, s_sq.shape[-1]
        if on_select is not None:
            on_select(path, sels)
        sel_ws = [int(s.shape[0]) for s in sels]
        k_leaf = max(sel_ws)
        widths[path] = k_leaf
        ffn_leaf = ffn_tree[seg][name]
        prs = []
        for i, sel in enumerate(sels):
            sh = compaction_shards(gcfg, sel_ws[i], F)
            # pad to the leaf width; per-shard pad only when the pad
            # target keeps every shard block whole
            if sh > 1 and k_leaf % sh:
                sh = 1
            idx_p, keep = selector_lib.pad_selection(sel, k_leaf, F, shards=sh)
            p_i = (
                {kk: v[i] for kk, v in ffn_leaf.items()} if scan
                else ffn_leaf
            )
            prs.append(_mask_dead_rows(compact(p_i, idx_p, shards=sh),
                                       keep))
        out.setdefault(seg, {})[name] = (
            {kk: jnp.stack([p[kk] for p in prs]) for kk in prs[0]}
            if scan else prs[0]
        )
    return out, widths


# ---------------------------------------------------------------------------
# The paged server's decode FF (serving/server.py): one buffer per FF
# layer holds the union of the live requests' selections, and each
# request keeps a 0/1 mask over it (kernels/griffin_ffn.py).
# ---------------------------------------------------------------------------

#: neurons per block of the union buffer (the kernel's BlockSpec rows)
UNION_BLOCK = 128


def union_rows(sels: List[np.ndarray], d_ff: int, groups: int = 1,
               block: int = UNION_BLOCK):
    """Lay out the union of several selections of one FF layer for the
    union kernel.

    The buffer has ``groups`` equal parts, one per contiguous F-range
    (the tensor-parallel shards; 1 on one device), each of capacity
    ``ceil(d_ff / groups / block)`` blocks.  Part ``j`` holds the union
    of the selections' indices inside range ``j`` at its head, padded by
    repeating an index.  Returns ``(idx [C] int32, masks [len(sels), C]
    float32 — each selection's neurons in the buffer, n_live [groups]
    int32 — the blocks each part uses, size — |U|)``.
    """
    fg = d_ff // groups
    cap = -(-fg // block) * block
    idx = np.empty(groups * cap, np.int32)
    masks = np.zeros((len(sels), groups * cap), np.float32)
    n_live = np.zeros(groups, np.int32)
    size = 0
    for j in range(groups):
        lo, at = j * fg, j * cap
        parts = [s[(s >= lo) & (s < lo + fg)] for s in sels]
        u = np.unique(np.concatenate(parts))
        c = len(u)
        idx[at:at + cap] = u[-1] if c else lo
        idx[at:at + c] = u
        for r, part in enumerate(parts):
            masks[r, at:at + c] = np.isin(u, part)
        n_live[j] = -(-c // block)
        size += c
    return idx, masks, n_live, size


def gather_union(ffn_tree: Any, idx_tree: Dict[str, Any]) -> Dict:
    """The union buffers: rows of the FF weights at ``idx_tree[path]``
    ([n, C], one row per scan instance), neuron-row-major
    ``{"wg", "w1", "w2": [n, C, D]}`` per layer, nested like
    ``ffn_tree`` (whose ``wg``/``w1`` are [D, F] and ``w2`` [F, D],
    with a leading [n] on scan leaves)."""
    out: Dict[str, Any] = {}
    for path, idx in idx_tree.items():
        seg, name = path.split("/")
        p = ffn_tree[seg][name]
        stack = (lambda v: v) if p["w1"].ndim == 3 else (lambda v: v[None])

        def cols(w, i):  # [D, F] -> [C, D]
            return jnp.take(w, i, axis=1).T

        def rows(w, i):  # [F, D] -> [C, D]
            return jnp.take(w, i, axis=0)

        out.setdefault(seg, {})[name] = {
            "wg": jax.vmap(cols)(stack(p["wg"]), idx),
            "w1": jax.vmap(cols)(stack(p["w1"]), idx),
            "w2": jax.vmap(rows)(stack(p["w2"]), idx),
        }
    return out
