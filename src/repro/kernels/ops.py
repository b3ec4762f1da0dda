"""Backend-dispatching wrappers around the Pallas kernels.

On TPU the Pallas kernels run compiled; everywhere else (this CPU
container, tests) they run with ``interpret=True`` or fall back to the
pure-jnp oracles in :mod:`repro.kernels.ref`.  The model code calls these
wrappers, never the kernels directly.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.kld_accept import fused_kld_accept
from repro.kernels.ngram_match import ngram_suffix_propose
from repro.kernels.ragged_attention import (
    paged_ragged_verify_attention, paged_ragged_verify_attention_quant,
    ragged_verify_attention)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def on_tpu() -> bool:
    """Trace-time backend check the model layer uses to pick between the
    Pallas data plane and the XLA reference path."""
    return _on_tpu()


# The serving plan whose mesh the kernel calls being traced run on (see
# :func:`sharded_kernels`); None off-mesh.
_PLAN: contextvars.ContextVar = contextvars.ContextVar("kernel_plan",
                                                       default=None)


@contextlib.contextmanager
def sharded_kernels(plan):
    """Trace the enclosed kernel calls per shard of ``plan.mesh``.

    GSPMD cannot partition a Pallas call, so under a serving mesh every
    kernel call runs inside a ``shard_map`` whose in/out specs come from
    ``plan`` (:class:`repro.launch.sharding.ServeMeshPlan`, which also
    lays out the pools): ``plan.paged_attention_specs(batch, kv_heads,
    quant)`` and ``plan.ngram_specs(batch)``.  Each chip then sweeps its
    own rows and KV heads of a pool that stays where the plan put it."""
    token = _PLAN.set(plan)
    try:
        yield
    finally:
        _PLAN.reset(token)


def _per_shard(fn, args: Tuple[jax.Array, ...], specs):
    """``fn(*args)``, per shard of the active plan's mesh when there is
    one; ``specs(plan) -> (in_specs, out_specs)``."""
    plan = _PLAN.get()
    if plan is None:
        return fn(*args)
    in_specs, out_specs = specs(plan)
    return jax.shard_map(fn, mesh=plan.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def ragged_attention(q: jax.Array, k_buf: jax.Array, v_buf: jax.Array,
                     q_pos: jax.Array, kv_pos: jax.Array, *,
                     window: Optional[int] = None,
                     force_kernel: bool = False,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Decode/verify attention against a ring KV cache (ragged lengths)."""
    if _on_tpu() or force_kernel:
        return ragged_verify_attention(
            q, k_buf, v_buf, q_pos, kv_pos, window=window,
            interpret=bool(interpret) if interpret is not None
            else not _on_tpu())
    return ref.ragged_verify_attention_ref(q, k_buf, v_buf, q_pos, kv_pos,
                                           window=window)


def paged_ragged_attention(q: jax.Array, pool_k: jax.Array,
                           pool_v: jax.Array, block_table: jax.Array,
                           q_pos: jax.Array, kv_pos: jax.Array, *,
                           window: Optional[int] = None,
                           force_kernel: bool = False,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Decode/verify attention straight off the block-paged KV pool."""
    if _on_tpu() or force_kernel:
        fn = functools.partial(
            paged_ragged_verify_attention, window=window,
            interpret=bool(interpret) if interpret is not None
            else not _on_tpu())
        return _per_shard(
            fn, (q, pool_k, pool_v, block_table, q_pos, kv_pos),
            lambda plan: plan.paged_attention_specs(
                q.shape[0], pool_k.shape[2], quant=False))
    return ref.paged_ragged_verify_attention_ref(q, pool_k, pool_v,
                                                 block_table, q_pos, kv_pos,
                                                 window=window)


def paged_ragged_attention_quant(q: jax.Array, pool_k: jax.Array,
                                 pool_v: jax.Array, k_scale: jax.Array,
                                 v_scale: jax.Array, block_table: jax.Array,
                                 q_pos: jax.Array, kv_pos: jax.Array, *,
                                 window: Optional[int] = None,
                                 force_kernel: bool = False,
                                 interpret: Optional[bool] = None
                                 ) -> jax.Array:
    """Decode/verify attention off the int8 block pool, dequantizing
    in-register inside the kv-sweep (DESIGN.md §13)."""
    if _on_tpu() or force_kernel:
        fn = functools.partial(
            paged_ragged_verify_attention_quant, window=window,
            interpret=bool(interpret) if interpret is not None
            else not _on_tpu())
        return _per_shard(
            fn, (q, pool_k, pool_v, k_scale, v_scale, block_table, q_pos,
                 kv_pos),
            lambda plan: plan.paged_attention_specs(
                q.shape[0], pool_k.shape[2], quant=True))
    return ref.paged_ragged_verify_attention_quant_ref(
        q, pool_k, pool_v, k_scale, v_scale, block_table, q_pos, kv_pos,
        window=window)


def ngram_propose(tokens: jax.Array, ctx_len: jax.Array, *, n: int, k: int,
                  force_kernel: bool = False,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Prompt-lookup suffix match for the n-gram drafter: most recent
    earlier occurrence of the trailing n-gram + its k-token continuation.
    Returns (proposed [B, K] int32 zero-padded, count [B] int32)."""
    if k == 0:
        b = tokens.shape[0]
        return jnp.zeros((b, 0), jnp.int32), jnp.zeros((b,), jnp.int32)
    if _on_tpu() or force_kernel:
        interp = bool(interpret) if interpret is not None else not _on_tpu()
        return _per_shard(
            lambda tok, ctx: ngram_suffix_propose(tok, ctx, n=n, k=k,
                                                  interpret=interp),
            (tokens, ctx_len),
            lambda plan: plan.ngram_specs(tokens.shape[0]))
    return ref.ngram_propose_ref(tokens, ctx_len, n=n, k=k)


def kld_accept_signals(target_logits: jax.Array, draft_logits: jax.Array,
                       draft_tokens: jax.Array, *,
                       force_kernel: bool = False,
                       interpret: Optional[bool] = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused per-position (KL(p||q), H(q), p(tok), q(tok))."""
    if _on_tpu() or force_kernel:
        return fused_kld_accept(
            target_logits, draft_logits, draft_tokens,
            interpret=bool(interpret) if interpret is not None
            else not _on_tpu())
    return ref.kld_accept_ref(target_logits, draft_logits, draft_tokens)
