"""Pallas TPU kernel: prompt-lookup suffix matching for the n-gram drafter.

The NGramDrafter (DESIGN.md §9) proposes draft tokens by finding the most
recent earlier occurrence of the sequence's trailing ``n``-gram inside its
own known text and replaying the tokens that followed it — zero draft
parameters, zero draft KV.  The hot loop is a batched windowed
string-match over int32 token buffers ``[B, L]``; on accelerators the
whole row fits in VMEM, so one program per sequence does all ``n``
shifted comparisons and every reduction on-chip instead of an XLA gather
pipeline.

Layout / grid
-------------
  ctx     [B]        int32   how many leading entries are real (scalar
                             prefetch: it lives in SMEM)
  shifted [B, n, L]  int32   plane j = the known text shifted left by j,
                             padded with -1 (never a token id); plane 0
                             is the text itself
  out     [B, 1, K]  int32   proposed continuation (zero-padded)
  cnt     [B, 1, 1]  int32   number of real proposals (0 = no match)

  grid = (B,) — one program per sequence; ``n``/``k`` are small static
  constants, so the shifted-equality reduction unrolls fully.  The
  wrapper builds the ``n`` shifted planes (a lane shift by a static
  offset is a plain XLA slice there), so the kernel compares whole
  ``[1, L]`` rows only.  All indexing is mask-and-reduce (no 1-D iota, no
  dynamic gather): the suffix values, the argmax-of-last-match, and the
  ``k`` continuation picks are each a broadcast compare + lane reduction.
  The unit axes keep every block's last two dims whole, as the TPU
  lowering requires.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ctx_ref, tok_ref, out_ref, cnt_ref, *, n: int, k: int, l: int):
    c = ctx_ref[pl.program_id(0)]                          # scalar int32
    row = tok_ref[0, 0:1, :]                               # [1, L]
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, l), 1)

    def pick(pos):
        # row[pos] as a masked lane reduction ([1, 1]; 0 when out of range)
        return jnp.sum(jnp.where(idx == pos, row, 0), axis=-1, keepdims=True)

    match = idx + n <= c - 1   # >= 1 known continuation (also kills the
    for j in range(n):         # trivial suffix occurrence, and c < n + 1)
        match = match & (tok_ref[0, j:j + 1, :] == pick(c - n + j))

    best = jnp.max(jnp.where(match, idx, -1), axis=-1, keepdims=True)
    cnt = jnp.where(best >= 0, jnp.minimum(k, c - (best + n)), 0)
    cnt_ref[0] = cnt.astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    out = jnp.zeros((1, k), jnp.int32)
    for m in range(k):
        tm = jnp.where(m < cnt, pick(best + n + m), 0)
        out = jnp.where(lane == m, tm, out)
    out_ref[0] = out


@functools.partial(jax.jit, static_argnames=("n", "k", "interpret"))
def ngram_suffix_propose(tokens: jax.Array, ctx_len: jax.Array, *, n: int,
                         k: int, interpret: bool = False
                         ) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, L] int32; ctx_len [B] int32.  Returns
    ``(proposed [B, K] int32 zero-padded, count [B] int32)`` — bit-exact
    against :func:`repro.kernels.ref.ngram_propose_ref`."""
    if n < 1:
        raise ValueError("suffix length must be >= 1")
    b, l = tokens.shape
    if k == 0:
        return (jnp.zeros((b, 0), jnp.int32),
                jnp.zeros((b,), jnp.int32))
    tokens = tokens.astype(jnp.int32)
    padded = jnp.pad(tokens, ((0, 0), (0, n - 1)), constant_values=-1)
    shifted = jnp.stack([padded[:, j:j + l] for j in range(n)], axis=1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, n, l), lambda bi, c: (bi, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, k), lambda bi, c: (bi, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi, c: (bi, 0, 0)),
        ],
    )
    out, cnt = pl.pallas_call(
        functools.partial(_kernel, n=n, k=k, l=l),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(ctx_len.astype(jnp.int32), shifted)
    return out[:, 0], cnt[:, 0, 0]
