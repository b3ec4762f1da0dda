"""Shared multi-row prefill programs (DESIGN.md §7).

Used by BOTH sides of a speculation round: the serving engine prefills
the target model with them, and :class:`repro.core.drafters.ModelDrafter`
prefills its draft model through the very same jitted entry points — so
a same-bucket admission group costs exactly one program per model, no
matter which component issues the call.

``prefill_rows`` builds fresh dense cache rows; ``prefill_paged_rows``
writes straight into allocated pool blocks through a multi-row
block-table view (pools donated — admission never copies the pool);
``prefill_paged_tail`` is its prefix-cache sibling — it computes only
the non-cached tail of each row, starting at the cached-coverage
offset, after running the round's batched copy-on-write block copies.
``set_slots`` scatters a batch-R row group into the batched cache at R
slots with one fused scatter per leaf.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.models import cache as cache_lib
from repro.models.transformer import forward

PyTree = Any

# cache leaves whose leading axis is the batch axis (everything else is
# [layers, batch, ...])
BATCH_AXIS0 = ("length", "kv_pos", "enc_valid", "block_table")


def set_slots(big: PyTree, rows: PyTree, idx: jax.Array) -> PyTree:
    """Scatter a batch=R cache-row group into the batched cache at the R
    slots ``idx`` (one fused scatter per leaf, not one per request)."""
    out = {}
    for k, v in big.items():
        r = rows[k]
        if k in BATCH_AXIS0:
            out[k] = v.at[idx].set(r)
        else:
            out[k] = v.at[:, idx].set(r)
    return out


def prefill_forward(params: PyTree, cfg: ModelConfig, cache: PyTree,
                    tokens: jax.Array, prompt_lens: jax.Array
                    ) -> Tuple[PyTree, jax.Array]:
    """Shared multi-row prefill tail: masked forward over the
    right-padded prompts [R, bucket], commit per-row ``length``, pick
    each row's last real token's logits."""
    mask = (jnp.arange(tokens.shape[1])[None] < prompt_lens[:, None])
    logits, cache, _ = forward(params, cfg, tokens, cache=cache,
                               mode="prefill", input_mask=mask)
    cache["length"] = prompt_lens.astype(jnp.int32)
    rows = jnp.arange(tokens.shape[0])
    last = logits[rows, jnp.maximum(prompt_lens - 1, 0)]
    return cache, last


@functools.partial(jax.jit, static_argnames=("cfg", "max_len", "plan"))
def prefill_rows(params: PyTree, cfg: ModelConfig, tokens: jax.Array,
                 prompt_lens: jax.Array, max_len: int,
                 plan=None) -> Tuple[PyTree, jax.Array]:
    """Prefill a same-bucket group of R requests into fresh cache rows in
    one program.  ``tokens [R, bucket]`` is right-padded; the (R, bucket)
    pair keys the compiled-program cache.  ``plan`` is an optional
    static :class:`repro.launch.sharding.ServeMeshPlan`: under a serving
    mesh the fresh rows are sharding-constrained to the §5 layouts at
    the program boundary (KV heads over *model*, rows over *data*), so
    the engine's scatter never round-trips them through replicated
    layouts.  Returns (cache rows [*, R, *], last_logits [R, V])."""
    cache = cache_lib.cache_struct(cfg, tokens.shape[0], max_len,
                                   jnp.float32)
    cache, last = prefill_forward(params, cfg, cache, tokens, prompt_lens)
    if plan is not None:
        cache = plan.cache_constraints(cache)
        last = jax.lax.with_sharding_constraint(last, plan.replicated())
    return cache, last


@functools.partial(jax.jit, static_argnames=("cfg", "plan"),
                   donate_argnames=("pool_k", "pool_v", "kv_pos",
                                    "k_scale", "v_scale"))
def prefill_paged_rows(params: PyTree, cfg: ModelConfig, pool_k: jax.Array,
                       pool_v: jax.Array, kv_pos: jax.Array,
                       table_rows: jax.Array, tokens: jax.Array,
                       prompt_lens: jax.Array, plan=None,
                       k_scale=None, v_scale=None
                       ) -> Tuple[PyTree, jax.Array]:
    """Prefill a same-bucket group of R requests *straight into their
    allocated pool blocks* as one multi-row program: the batch-R cache
    view aliases the shared pools and routes every row's KV writes
    through that row of ``table_rows [R, max_blocks]`` — rows land in
    disjoint blocks by construction.  The pools are donated — the caller
    immediately replaces its references with the returned ones, so
    admission never copies (or transiently doubles) the whole pool.
    Returns (cache view with updated pools + fresh per-row state,
    last_logits [R, V]).  ``plan`` (static) pins the returned pools /
    rows to the serving mesh's §5 layouts, exactly as in
    :func:`prefill_rows`.  ``k_scale``/``v_scale`` (donated) are the
    int8 pool's amax scale arrays — passing them makes the view a
    quantized cache, so the prefill writes quantize on the way in."""
    cache = cache_lib.paged_prefill_view(cfg, pool_k, pool_v, kv_pos,
                                         table_rows, k_scale=k_scale,
                                         v_scale=v_scale)
    cache, last = prefill_forward(params, cfg, cache, tokens, prompt_lens)
    if plan is not None:
        cache = plan.cache_constraints(cache)
        last = jax.lax.with_sharding_constraint(last, plan.replicated())
    return cache, last


@functools.partial(jax.jit, static_argnames=("cfg", "plan"),
                   donate_argnames=("pool_k", "pool_v", "kv_pos",
                                    "k_scale", "v_scale"))
def prefill_paged_tail(params: PyTree, cfg: ModelConfig, pool_k: jax.Array,
                       pool_v: jax.Array, kv_pos: jax.Array,
                       table_rows: jax.Array, tokens: jax.Array,
                       start_lens: jax.Array, tail_lens: jax.Array,
                       cow_src: jax.Array, cow_dst: jax.Array, plan=None,
                       k_scale=None, v_scale=None
                       ) -> Tuple[PyTree, jax.Array]:
    """Partial-prefix prefill (DESIGN.md §12): one multi-row program that
    computes only the non-cached tail of each request.

    Row ``r`` starts at its cached coverage ``start_lens[r]`` — the view
    is built with the per-row length preset, so the decode-mode forward
    positions the ``tokens [R, bucket]`` tail at ``start + arange`` and
    attends over the gathered pool view, i.e. straight THROUGH the
    shared prefix blocks the scheduler mapped into ``table_rows``.
    ``tail_lens`` masks the right padding out of the KV writes
    (``write_mask``), and the batched copy-on-write pairs
    ``cow_src/cow_dst [R]`` (sentinel ``num_blocks`` = no copy) run
    first so a row whose tail rewrites the last position of a shared
    block lands in its private fork.  Cold rows degrade gracefully
    (start 0, tail = full prompt) but the engine keeps them on
    :func:`prefill_paged_rows` so the cold path stays program-identical
    with the pre-cache engine.  Recurrent families never reach here —
    the engine gates prefix caching on attention-only stacks, whose
    cache state is exactly the pool the shared blocks live in.

    The pools are donated and the returned view is scattered back with
    :func:`scatter_paged_rows`, same as the cold entry point.  Under the
    int8 pool (``k_scale``/``v_scale`` given, donated) the COW prologue
    carries the scale arrays with their blocks and the view quantizes
    the tail writes."""
    pool_k, pool_v, kv_pos = cache_lib.copy_blocks(pool_k, pool_v, kv_pos,
                                                   cow_src, cow_dst)
    if k_scale is not None:
        k_scale, v_scale = cache_lib.copy_scales(k_scale, v_scale,
                                                 cow_src, cow_dst)
    cache = cache_lib.paged_prefill_view(cfg, pool_k, pool_v, kv_pos,
                                         table_rows, lengths=start_lens,
                                         k_scale=k_scale, v_scale=v_scale)
    t = tokens.shape[1]
    write_mask = jnp.arange(t)[None] < tail_lens[:, None]
    # a decode-mode forward reaches the paged Pallas kernel on a TPU,
    # which runs per shard of the plan's mesh (kernels/ops.py)
    sharded = (contextlib.nullcontext() if plan is None else
               kernel_ops.sharded_kernels(plan))
    with sharded:
        logits, cache, _ = forward(params, cfg, tokens, cache=cache,
                                   mode="decode", write_mask=write_mask)
    cache["length"] = (start_lens + tail_lens).astype(jnp.int32)
    rows = jnp.arange(tokens.shape[0])
    last = logits[rows, jnp.maximum(tail_lens - 1, 0)]
    if plan is not None:
        cache = plan.cache_constraints(cache)
        last = jax.lax.with_sharding_constraint(last, plan.replicated())
    return cache, last


def scatter_paged_rows(big: PyTree, rows: PyTree, idx: jax.Array) -> PyTree:
    """Fold a ``prefill_paged_rows`` result back into the batched paged
    cache: pool leaves are replaced wholesale (the donated pools came
    back updated), per-row leaves (length, hybrid recurrent state) are
    scattered at ``idx``."""
    out = dict(big)
    out["k"], out["v"] = rows["k"], rows["v"]
    out["kv_pos"] = rows["kv_pos"]
    if "k_scale" in big:                 # int8 pool scales travel with it
        out["k_scale"], out["v_scale"] = rows["k_scale"], rows["v_scale"]
    out["length"] = big["length"].at[idx].set(rows["length"])
    for key in ("lru", "conv"):        # hybrid recurrent rows stay dense
        if key in big:
            out[key] = big[key].at[:, idx].set(rows[key])
    return out
