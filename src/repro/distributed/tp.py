"""shard_map tensor parallelism for the paged serving path.

One mesh axis (``model`` by default) carries head-parallel attention and
FF-hidden-parallel FFN through the whole paged step (DESIGN.md section
11):

* **KV page pools** shard along ``kv_heads`` — each shard holds its KV
  heads' slice of every page, so per-shard pool bytes shrink ∝ 1/N and
  the fused paged-attention kernel (``kernels/paged_attn.py``) runs
  unchanged on its local head slice (heads are independent in the
  online softmax; the grid just has KV/N head steps).  Block tables,
  positions, write masks and owned-page counts are replicated — they
  are host-scheduler state every shard must agree on.
* **Attention projections** shard along ``heads``/``kv_heads``; the
  out-projection's contraction over heads becomes a partial sum that
  ``sharding.psum_if_tp`` all-reduces (the only attention collective).
* **FF weights** shard along the hidden axis, including the per-slot
  GRIFFIN-compacted expert weights: selection pads ``k_ff`` to a
  multiple of the axis size (``GriffinConfig.k_of``) and balanced
  per-shard top-k (``selector.select_topk_per_shard``) puts exactly
  ``k/N`` experts in each shard's contiguous F-range, so the compacted
  decode runs all-gather-free — one psum after the down-projection,
  same as the dense path.
* **Everything else is replicated** (embed table, LM head, norms,
  residual stream), so logits come out replicated and the host
  scheduler/sampler stay device-count-agnostic.

The per-shard program is the ordinary ``decoder.decode_step_paged`` /
``verify_step_paged`` body traced with a *local* config (head counts
divided by the shard count) inside a ``sharding.tp_axis`` scope that
turns ``psum_if_tp`` into real collectives.  The single-device path is
the same code with the scope inactive — it stays the differential
oracle the identity tests compare against
(``tests/test_sharded_serving.py``).

GRIFFIN statistics are shard-local along F inside the step; the
prefill wrapper all-gathers them (tiled, shard-major == global F order)
so the host-side selection sees the same global ``[B, F]`` statistic a
single-device run produces.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shlib
from repro.distributed.sharding import mesh_axis_size
from repro.models import decoder
from repro.models.param import tree_map_specs

# innermost-dict leaf names of a (per-slot) compacted FF tree -> the
# logical axes of the *trailing* dims; leading dims (slot axis, scan
# layer axis) are replicated
_PRUNED_AXES = {
    "w1": ("embed", "mlp"),
    "wg": ("embed", "mlp"),
    "w2": ("mlp", "embed"),
    "b1": ("mlp",),
    "bg": ("mlp",),
    "b2": ("act_embed",),
}


def gather_stats(stats: Any, axis: str) -> Any:
    """All-gather shard-local GRIFFIN stats to the global layout.

    ``s_sq``/``z_sq`` are partitioned along F (shard j holds the
    contiguous F-block j, matching the NamedSharding device order), so
    a tiled all-gather along the last axis reconstructs the exact
    global column order; ``x_sq`` is already replicated.
    """
    if stats is None:
        return None

    def one(leaf: Dict) -> Dict:
        out = dict(leaf)
        for k in ("s_sq", "z_sq"):
            if k in out:
                out[k] = jax.lax.all_gather(
                    out[k], axis, axis=out[k].ndim - 1, tiled=True
                )
        return out

    return jax.tree.map(
        one, stats, is_leaf=lambda x: isinstance(x, dict) and "s_sq" in x
    )


def pool_shard_bytes(pools: Any) -> int:
    """Bytes of KV pool resident on ONE device (= total/N when the
    kv_heads axis is sharded N ways; = total bytes on a single device)."""
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(pools):
        shape = leaf.sharding.shard_shape(leaf.shape) \
            if hasattr(leaf, "sharding") else leaf.shape
        total += int(np.prod(shape)) * leaf.dtype.itemsize
    return total


class PagedTP:
    """Builds and caches the shard_mapped + jitted paged step functions.

    The factory owns the resolved PartitionSpec trees (params, pools,
    per-slot compacted FF) and the local config; the server calls
    ``prefill``/``decode``/``verify``/``cow`` exactly like its
    single-device jits (pools donated on every step, so the in-place
    page updates compose with the NamedShardings).
    """

    def __init__(self, cfg, mesh: Mesh, *, axis: str = "model",
                 backend: str = "gather", kv_dtype: str = "fp32"):
        self.cfg, self.mesh, self.axis, self.backend = cfg, mesh, axis, backend
        # quantized pools shard transparently: the scale pools carry the
        # same ("pages", "kv_heads", None, None) axes as the data, so
        # each shard holds its heads' scales (per-shard scale bytes 1/N)
        # and the per-shard step runs the identical quantize program on
        # its local head slice
        self.kv_dtype = kv_dtype
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no {axis!r} axis")
        n = mesh_axis_size(mesh, axis)
        self.n = n
        bad = {
            name: dim
            for name, dim in (
                ("num_heads", cfg.num_heads),
                ("num_kv_heads", cfg.num_kv_heads),
                ("d_ff", cfg.d_ff),
            )
            if dim % n != 0
        }
        if bad:
            raise ValueError(
                f"{cfg.name}: tensor-parallel paged serving needs every "
                f"sharded dim divisible by the {axis!r} axis (size {n}); "
                f"got {bad}. Head-axis sharding cannot replicate-fallback "
                f"here — the per-shard psums assume real partitioning."
            )
        self.cfg_local = cfg.replace(
            num_heads=cfg.num_heads // n, num_kv_heads=cfg.num_kv_heads // n
        )
        # logical shard ids for per-shard step-time attribution
        # (obs.stragglers): single-process SPMD steps are synchronous,
        # so the host wall time is charged to every shard — an upper
        # bound per shard; a real multi-host deployment records each
        # process's own shard time instead
        self.shard_ids = tuple(range(n))
        self.rules = shlib.make_paged_tp_rules(axis)
        self.param_specs = tree_map_specs(
            lambda s: shlib.spec_for(s.axes, self.rules, mesh, s.shape),
            decoder.model_specs(cfg),
        )
        self._steps: Dict[Any, Callable] = {}

    # -- spec trees --------------------------------------------------------
    def pool_pspecs(self, num_pages: int, page_size: int) -> Any:
        return tree_map_specs(
            lambda s: shlib.spec_for(s.axes, self.rules, self.mesh, s.shape),
            decoder.paged_pool_specs(self.cfg, num_pages, page_size,
                                     self.kv_dtype),
        )

    def pruned_pspecs(self, pruned: Any) -> Any:
        """PartitionSpec tree for a per-slot compacted FF tree (leading
        slot / scan-layer dims replicated, trailing dims per
        ``_PRUNED_AXES``).  The compacted width must divide the axis —
        the selection guarantees it (``GriffinConfig.k_of`` with
        ``tp_shards``); a non-divisible width here is a config error,
        not a replicate-fallback case."""

        def leaf(path: str, key: str, arr) -> P:
            axes = _PRUNED_AXES[key]
            full = (None,) * (arr.ndim - len(axes)) + axes
            spec = shlib.spec_for(full, self.rules, self.mesh, arr.shape)
            if key != "b2" and self.axis not in jax.tree.leaves(tuple(spec)):
                raise ValueError(
                    f"compacted FF leaf {path}/{key} with shape {arr.shape} "
                    f"is not divisible by the {self.axis!r} axis "
                    f"(size {self.n}) — the divisible-k_ff rule holds per "
                    f"layer: pass a GriffinConfig with tp_shards={self.n} "
                    f"(tier budgets pad each layer's k to a multiple, see "
                    f"griffin.tier_k)."
                )
            return spec

        return {
            seg: {
                name: {k: leaf(f"{seg}/{name}", k, v) for k, v in ffn.items()}
                for name, ffn in layers.items()
            }
            for seg, layers in pruned.items()
        }

    # -- placement ---------------------------------------------------------
    def _shard(self, tree: Any, pspecs: Any) -> Any:
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
        return jax.device_put(tree, shardings)

    def shard_params(self, params: Any) -> Any:
        return self._shard(params, self.param_specs)

    def shard_pools(self, pools: Any, num_pages: int, page_size: int) -> Any:
        return self._shard(pools, self.pool_pspecs(num_pages, page_size))

    def shard_pruned(self, pruned: Any) -> Any:
        return self._shard(pruned, self.pruned_pspecs(pruned))

    # -- step functions ----------------------------------------------------
    def _wrap(self, fn, in_specs, out_specs, donate: Tuple[int, ...]):
        return jax.jit(
            jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False),
            donate_argnums=donate,
        )

    def _pruned_key(self, pruned: Any) -> Any:
        # structure AND shapes: tier buckets re-size the compacted width
        # between ticks, and a step factory built for one width must not
        # serve another (its in_specs were resolved against the shapes
        # it first saw)
        if pruned is None:
            return None
        return (
            jax.tree.structure(pruned),
            tuple(a.shape for a in jax.tree.leaves(pruned)),
        )

    def prefill(self, pool_specs: Any, collect: bool, pruned: Any) -> Callable:
        key = ("prefill", collect, self._pruned_key(pruned))
        if key not in self._steps:
            cfg_l, axis, backend = self.cfg_local, self.axis, self.backend
            kv_dtype = self.kv_dtype

            def local(params, pools, bt, tokens, pos, mask, pr):
                with shlib.tp_axis(axis):
                    logits, new_pools, stats = decoder.decode_step_paged(
                        params, cfg_l, pools, bt, tokens, pos,
                        write_mask=mask, pruned=pr, collect_stats=collect,
                        backend=backend, kv_dtype=kv_dtype,
                    )
                return logits, new_pools, gather_stats(stats, axis)

            pr_specs = P() if pruned is None else self.pruned_pspecs(pruned)
            self._steps[key] = self._wrap(
                local,
                (self.param_specs, pool_specs, P(), P(), P(), P(), pr_specs),
                (P(), pool_specs, P()),
                donate=(1,),
            )
        return self._steps[key]

    def decode(self, pool_specs: Any, pruned: Any) -> Callable:
        key = ("decode", self._pruned_key(pruned))
        if key not in self._steps:
            cfg_l, axis, backend = self.cfg_local, self.axis, self.backend
            kv_dtype = self.kv_dtype

            def local(params, pools, bts, toks, pos, mask, pr):
                with shlib.tp_axis(axis):
                    logits, new_pools, _ = decoder.decode_step_paged(
                        params, cfg_l, pools, bts, toks, pos,
                        write_mask=mask, pruned=pr, backend=backend,
                        kv_dtype=kv_dtype,
                    )
                return logits, new_pools

            pr_specs = P() if pruned is None else self.pruned_pspecs(pruned)
            self._steps[key] = self._wrap(
                local,
                (self.param_specs, pool_specs, P(), P(), P(), P(), pr_specs),
                (P(), pool_specs),
                donate=(1,),
            )
        return self._steps[key]

    def draft_verify(self, pool_specs: Any, pruned: Any, num_steps: int,
                     spec_k: int) -> Callable:
        """Fused speculative round (``decoder.draft_verify_paged``) —
        draft scan plus dense verify in one program — shard_mapped like
        ``decode``: the scan body's logits come out replicated (psum
        after out-/down-projection), so every shard's in-scan ``argmax``
        feedback picks the same token, the on-device verify matrix is
        identical across shards, and the drafts + verify logits are
        replicated host-visible state — token-identity with the
        single-device round holds by the same argument as every other
        step.  ``num_steps`` is static (one program per distinct padded
        round length, bounded by log2(spec_k)+1)."""
        key = ("draft_verify", self._pruned_key(pruned), num_steps, spec_k)
        if key not in self._steps:
            cfg_l, axis, backend = self.cfg_local, self.axis, self.backend
            kv_dtype = self.kv_dtype

            def local(params, pools, bts, toks, pos, ks, live, pr):
                with shlib.tp_axis(axis):
                    drafts, vlogits, new_pools = decoder.draft_verify_paged(
                        params, cfg_l, pools, bts, toks, pos, ks, live,
                        pruned=pr, num_steps=num_steps, spec_k=spec_k,
                        backend=backend, kv_dtype=kv_dtype,
                    )
                return drafts, vlogits, new_pools

            pr_specs = P() if pruned is None else self.pruned_pspecs(pruned)
            self._steps[key] = self._wrap(
                local,
                (self.param_specs, pool_specs, P(), P(), P(), P(), P(),
                 pr_specs),
                (P(), P(), pool_specs),
                donate=(1,),
            )
        return self._steps[key]

    def probe(self, pool_specs: Any) -> Callable:
        """Dense stats-only decode step for flocking telemetry
        (``obs.flocking``): runs the un-pruned model with
        ``collect_stats`` over the live paged KV and returns only the
        all-gathered statistic tree.  Pools are **not** donated — the
        caller discards the step's writes, so serving state is
        untouched and the next real decode sees identical pools."""
        key = ("probe",)
        if key not in self._steps:
            cfg_l, axis, backend = self.cfg_local, self.axis, self.backend
            kv_dtype = self.kv_dtype

            def local(params, pools, bts, toks, pos, mask):
                with shlib.tp_axis(axis):
                    _, _, stats = decoder.decode_step_paged(
                        params, cfg_l, pools, bts, toks, pos,
                        write_mask=mask, pruned=None, collect_stats=True,
                        backend=backend, kv_dtype=kv_dtype,
                    )
                return gather_stats(stats, axis)

            self._steps[key] = self._wrap(
                local,
                (self.param_specs, pool_specs, P(), P(), P(), P()),
                P(),
                donate=(),
            )
        return self._steps[key]

    def verify(self, pool_specs: Any) -> Callable:
        key = ("verify",)
        if key not in self._steps:
            cfg_l, axis, backend = self.cfg_local, self.axis, self.backend
            kv_dtype = self.kv_dtype

            def local(params, pools, bts, toks, pos, mask):
                with shlib.tp_axis(axis):
                    return decoder.verify_step_paged(
                        params, cfg_l, pools, bts, toks, pos, mask,
                        backend=backend, kv_dtype=kv_dtype,
                    )

            self._steps[key] = self._wrap(
                local,
                (self.param_specs, pool_specs, P(), P(), P(), P()),
                (P(), pool_specs),
                donate=(1,),
            )
        return self._steps[key]

    def cow(self, pool_specs: Any) -> Callable:
        key = ("cow",)
        if key not in self._steps:
            cfg = self.cfg  # page copies are head-count agnostic

            def local(pools, src, dst):
                return decoder.copy_pool_pages(cfg, pools, src, dst)

            self._steps[key] = self._wrap(
                local, (pool_specs, P(), P()), pool_specs, donate=(0,)
            )
        return self._steps[key]
