"""Per-(workload x mesh) sharding rules — MaxText-style logical axes.

The baseline layouts (DESIGN.md §5):

* train    — FSDP: ``embed`` over *data*, ``mlp/heads/vocab`` over *model*,
             batch over (pod, data), sequence-parallel residual stream
             (seq over *model* between blocks) to bound remat stashes.
* prefill  — serving TP: weights over *model* only (replicated over data),
             batch over (pod, data).
* decode   — serving TP; KV cache batch-sharded over (pod, data); KV heads
             over *model* (GSPMD uneven sharding reproduces vLLM's KV-head
             replication when kv_heads < 16).
* long decode (batch=1) — batch unshardable; state/ring caches replicated
  over data; heads over model.  (Sequence-parallel cache is a hillclimb
  variant, see EXPERIMENTS.md §Perf.)
* serve    — the ServingEngine's live data plane (:func:`serve_rules`):
  params TP over *model*, batch slots over *data*, KV heads over *model*
  under the uneven-head guard, block tables / control vectors
  replicated.  Consumed by the engine's mesh path (DESIGN.md §5), not
  just the dry-run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.config import InputShape, ModelConfig, ShardingConfig
from repro.models import cache as cache_lib
from repro.models.module import param_shardings
from repro.models.transformer import model_specs

PyTree = Any


def canonical_spec(*parts) -> P:
    """THE PartitionSpec constructor (speclint JX003): trims trailing
    ``None`` dims and writes a one-axis tuple ``('data',)`` as its bare
    axis name ``'data'``, so equal layouts are structurally equal.

    Jit signatures compare PartitionSpecs *structurally* —
    ``P('data', None)`` and ``P('data')`` describe the same sharding but
    hash and compare differently, so a program keyed on one and re-fed
    the other silently forks the compiled-program cache (PR 5's serving
    round recompiled every round until its no-recompile guard tripped).
    Canonical form makes that hazard unrepresentable; every spec literal
    in the tree must be built here (trailing-``None`` literals anywhere
    else are JX003 findings)."""
    out = [p[0] if isinstance(p, tuple) and len(p) == 1 else p
           for p in parts]
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _batch_axes(mesh: Mesh, global_batch: int) -> Tuple[str, ...]:
    """Largest prefix of (pod, data) whose product divides the batch."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    chosen: list = []
    prod = 1
    for a in axes:
        if global_batch % (prod * sizes[a]) == 0:
            chosen.append(a)
            prod *= sizes[a]
    return tuple(chosen)


def make_rules(mesh: Mesh, shape: InputShape, *,
               expert_parallel: bool = False,
               cache_seq_axis: Optional[str] = "model") -> ShardingConfig:
    train = shape.kind == "train"
    return ShardingConfig(
        batch=_batch_axes(mesh, shape.global_batch),
        heads="model",
        mlp="model",
        vocab="model",
        embed="data" if train and "data" in mesh.axis_names else None,
        # KV caches are sequence-sharded: kv_heads rarely divide the model
        # axis, and the cache dominates decode/prefill memory (DESIGN.md §5)
        cache_seq=cache_seq_axis if shape.kind in ("decode", "prefill")
        else None,
        experts="model" if expert_parallel else None,
        seq="model" if train else None,
    )


# ---------------------------------------------------------------------------
# Cache shardings
# ---------------------------------------------------------------------------

_CACHE_AXES = {
    # leaf name -> logical axes per dim
    "length": ("batch",),
    "kv_pos": ("batch", "cache_seq"),
    "k": ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
    "v": ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
    "cross_k": ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
    "cross_v": ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
    "enc_valid": ("batch", "cache_seq"),
    "ssd": ("layers", "batch", "heads", "head_dim", "state"),
    "conv": ("layers", "batch", "conv", "mlp"),
    "lru": ("layers", "batch", "mlp"),
}


def cache_shardings(cache_tree: PyTree, mesh: Mesh,
                    rules: ShardingConfig) -> PyTree:
    from repro.models.module import logical_to_pspec
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(name: str, leaf) -> NamedSharding:
        axes = _CACHE_AXES[name]
        pspec = logical_to_pspec(axes, rules)
        parts = list(tuple(pspec) + (None,) * (len(leaf.shape) - len(pspec)))
        fixed = []
        used: set = set()
        for dim, part in zip(leaf.shape, parts):
            if part is None:
                fixed.append(None)
                continue
            names = part if isinstance(part, tuple) else (part,)
            size = 1
            for nm in names:
                size *= axis_sizes[nm]
            # each mesh axis may appear at most once per spec (e.g. MHA
            # caches where kv_heads and cache_seq both map to 'model')
            if dim % size != 0 or any(nm in used for nm in names):
                fixed.append(None)
                continue
            used.update(names)
            fixed.append(part)
        return NamedSharding(mesh, canonical_spec(*fixed))

    return {k: one(k, v) for k, v in cache_tree.items()}


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, rules: ShardingConfig,
                   ndim: int) -> NamedSharding:
    spec = [tuple(rules.batch) if rules.batch else None] + [None] * (ndim - 1)
    return NamedSharding(mesh, canonical_spec(*spec))


def activation_sharding(mesh: Mesh, rules: ShardingConfig) -> Optional[NamedSharding]:
    """[B, S, d] residual-stream constraint used in train mode."""
    if rules.seq is None:
        return None
    return NamedSharding(
        mesh, canonical_spec(tuple(rules.batch) if rules.batch else None,
                             rules.seq, None))


def attn_head_sharding(mesh: Mesh, rules: ShardingConfig):
    """([B, T, H, D] NamedSharding, head-axis size) for the TP constraint
    pinned on q/k/v inside the attention sublayer."""
    if rules.heads is None:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return (NamedSharding(
        mesh, canonical_spec(tuple(rules.batch) if rules.batch else None,
                             None, rules.heads, None)),
        sizes[rules.heads])


# ---------------------------------------------------------------------------
# Serving mesh (DESIGN.md §5): the ServingEngine's live data plane
# ---------------------------------------------------------------------------

def serve_rules(mesh: Mesh, global_batch: int) -> ShardingConfig:
    """The ``serve`` rule set: tensor-parallel params over *model*
    (replicated over data), batch slots over *data* (when the batch
    divides), KV heads over *model* under :func:`kv_head_axis`'s uneven
    guard, and ``cache_seq`` unsharded — the paged pool's block axis
    must stay whole because block tables address ANY pool block."""
    return ShardingConfig(
        batch=_batch_axes(mesh, global_batch),
        heads="model", mlp="model", vocab="model",
        embed=None, cache_seq=None, experts=None, seq=None)


def _axes_size(mesh: Mesh, names) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    names = names if isinstance(names, tuple) else (names,)
    return int(np.prod([sizes[n] for n in names]))


def kv_head_axis(n_kv_heads: int, mesh: Mesh,
                 rules: ShardingConfig) -> Optional[str]:
    """Uneven-KV-head guard: GQA miniatures carry 1–2 (padded) KV heads,
    which rarely divide the model axis, and jit ``in_shardings`` demand
    even tiling — so such caches REPLICATE their head dim (vLLM's
    KV-head replication) instead of sharding it."""
    if rules.heads is None or rules.heads not in mesh.axis_names:
        return None
    return rules.heads if n_kv_heads % _axes_size(mesh, rules.heads) == 0 \
        else None


def serve_cache_shardings(cache: PyTree, mesh: Mesh,
                          rules: ShardingConfig) -> PyTree:
    """NamedSharding per leaf of a *serving* cache pytree — dense rows,
    paged pools, per-row prefill groups, or a drafter's token buffer,
    keyed by leaf name + shape.  Layout contract (DESIGN.md §5):

    * KV buffers: head dim over *model* (uneven counts replicate);
      dense rows additionally shard batch over *data*; paged POOLS keep
      the block axis whole — any sequence's table may address any
      block, so sharding blocks over data would turn every gather into
      cross-device traffic.
    * recurrent rows (ssd/lru/conv) and the ngram token history: batch
      over *data*.
    * every int32 control leaf (length, kv_pos maps, block tables,
      enc_valid): replicated — the host rewrites those rows piecemeal
      each round and every shard needs the full table to address the
      shared pool.
    """
    paged = isinstance(cache, dict) and "block_table" in cache
    data = tuple(rules.batch) if rules.batch else None

    def bp(dim: int):
        if data is None or dim % _axes_size(mesh, data) != 0:
            return None
        return data

    def one(name: str, leaf) -> NamedSharding:
        s = leaf.shape
        if name in ("k", "v", "cross_k", "cross_v"):
            kvp = kv_head_axis(s[3], mesh, rules)
            if paged:            # pool [L, n_blocks, bs, KV, D]
                return NamedSharding(
                    mesh, canonical_spec(None, None, None, kvp))
            return NamedSharding(
                mesh, canonical_spec(None, bp(s[1]), None, kvp))
        if name in ("k_scale", "v_scale"):
            # int8 pool scales [L, n_blocks, bs, KV]: KV heads follow
            # their value pool's model-axis split, block axis whole
            kvp = kv_head_axis(s[3], mesh, rules)
            return NamedSharding(
                mesh, canonical_spec(None, None, None, kvp))
        if name in ("ssd", "lru", "conv"):       # [L, B, ...] per-slot rows
            return NamedSharding(mesh, canonical_spec(None, bp(s[1])))
        if name == "tokens":                     # ngram history [B, H]
            return NamedSharding(mesh, canonical_spec(bp(s[0])))
        return NamedSharding(mesh, P())
    return {k: one(k, v) for k, v in cache.items()}


def round_state_shardings(state: PyTree, mesh: Mesh,
                          rules: ShardingConfig) -> PyTree:
    """RoundState-shaped pytree of NamedShardings — the serving round's
    jit ``in_shardings``/``out_shardings``.  Caches go through
    :func:`serve_cache_shardings`; every [B] control leaf (pending /
    sl_next / seed / round_idx / done / tokens_budget / eos_id), the
    base key, and the policy state replicate: they are tiny, the host
    rewrites them per admission, and replication keeps the bucket pick
    and the engine's eager per-slot updates free of cross-device
    layout churn."""
    rep = NamedSharding(mesh, P())

    def cache_sh(tree):
        if isinstance(tree, dict):
            return serve_cache_shardings(tree, mesh, rules)
        return jax.tree_util.tree_map(lambda _: rep, tree)

    return state._replace(
        target_cache=cache_sh(state.target_cache),
        draft_cache=cache_sh(state.draft_cache),
        policy_state=jax.tree_util.tree_map(lambda _: rep,
                                            state.policy_state),
        pending=rep, sl_next=rep, key=rep, seed=rep, round_idx=rep,
        done=rep, tokens_budget=rep, eos_id=rep)


@dataclasses.dataclass(frozen=True)
class ServeMeshPlan:
    """Hashable (mesh, rules) bundle the engine threads through the
    jitted serving entry points as a STATIC argument.  Prefill programs
    call :meth:`cache_constraints` on their fresh cache rows / pools so
    GSPMD pins the §5 layouts at the program boundary instead of
    round-tripping freshly written KV through replicated layouts.

    (Both fields are hashable — ``Mesh`` implements ``__hash__``,
    ``ShardingConfig`` is a frozen dataclass — so equal plans hit the
    same compiled program.)"""
    mesh: Mesh
    rules: ShardingConfig

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def cache_constraints(self, cache: PyTree) -> PyTree:
        return jax.lax.with_sharding_constraint(
            cache, serve_cache_shardings(cache, self.mesh, self.rules))

    def _rows(self, batch: int):
        data = tuple(self.rules.batch) if self.rules.batch else None
        if data is None or batch % _axes_size(self.mesh, data):
            return None
        return data

    def paged_attention_specs(self, batch: int, kv_heads: int, *,
                              quant: bool):
        """``shard_map`` (in_specs, out_spec) of a paged verify kernel
        call (``kernels/ops.py``): ``q [B,T,H,D]``, the pools
        ``[N,BS,KV,D]`` (+ int8 scales ``[N,BS,KV]``), ``block_table
        [B,cols]``, ``q_pos [B,T]``, ``kv_pos [N,BS]``.  Rows go over
        *data* when they divide it; KV heads, and the query heads they
        serve, over *model* by :func:`kv_head_axis` — the rule that laid
        out the pool, so each chip sweeps the heads it already holds and
        no pool is gathered.  The block axis stays whole."""
        rows = self._rows(batch)
        heads = kv_head_axis(kv_heads, self.mesh, self.rules)
        q = canonical_spec(rows, None, heads)
        pool = canonical_spec(None, None, heads)
        ctl = canonical_spec(rows)
        pools = (pool,) * (4 if quant else 2)
        return (q, *pools, ctl, ctl, P()), q

    def ngram_specs(self, batch: int):
        """``shard_map`` specs of the n-gram kernel: ``tokens [B,L]`` and
        ``ctx_len [B]`` in, ``[B,K]`` / ``[B]`` out, rows over *data*."""
        rows = canonical_spec(self._rows(batch))
        return (rows, rows), (rows, rows)


def moe_shardings(mesh: Mesh, rules: ShardingConfig):
    """Dispatch-buffer constraints for moe_apply: capacity dim over the
    batch axes, token dim likewise."""
    b = tuple(rules.batch) if rules.batch else None
    if b is None:
        return None
    return {"cap": NamedSharding(mesh, canonical_spec(None, b, None)),
            "tok": NamedSharding(mesh, canonical_spec(b, None))}
