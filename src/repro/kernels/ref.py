"""Pure-jnp oracles for the Pallas kernels (the allclose ground truth)."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def ragged_verify_attention_ref(q: jax.Array, k_buf: jax.Array,
                                v_buf: jax.Array, q_pos: jax.Array,
                                kv_pos: jax.Array,
                                window: Optional[int] = None) -> jax.Array:
    """Oracle for the ragged decode/verify attention kernel.

    q [B,T,H,D] — T = 1 (decode) or SL_cap+1 (verification);
    k_buf/v_buf [B,W,KV,D] — ring-buffer cache (already containing the new
    tokens' KV);  q_pos [B,T] absolute positions; kv_pos [B,W] slot
    positions (-1 = empty).  GQA via head grouping.
    """
    b, t, h, d = q.shape
    kv = k_buf.shape[2]
    g = h // kv
    qr = q.reshape(b, t, kv, g, d).astype(jnp.float32)
    scores = jnp.einsum("btkgd,bskd->bkgts", qr,
                        k_buf.astype(jnp.float32)) / math.sqrt(d)
    mask = (kv_pos[:, None, :] >= 0) & (kv_pos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        mask = mask & (q_pos[:, :, None] - kv_pos[:, None, :] < window)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v_buf.astype(jnp.float32))
    return out.reshape(b, t, h, d).astype(q.dtype)


def paged_ragged_verify_attention_ref(q: jax.Array, pool_k: jax.Array,
                                      pool_v: jax.Array,
                                      block_table: jax.Array,
                                      q_pos: jax.Array, kv_pos: jax.Array,
                                      window: Optional[int] = None
                                      ) -> jax.Array:
    """Oracle for the block-paged kernel: gather each sequence's view out
    of the pool through its block table, then run the dense oracle.

    pool_k/pool_v [N, BS, KV, D]; block_table [B, MAXB] (-1 =
    unallocated); kv_pos [N, BS] pool-level (-1 = empty)."""
    b, maxb = block_table.shape
    bs = pool_k.shape[1]
    idx = jnp.maximum(block_table, 0)
    k_view = pool_k[idx].reshape((b, maxb * bs) + pool_k.shape[2:])
    v_view = pool_v[idx].reshape((b, maxb * bs) + pool_v.shape[2:])
    pos = jnp.where((block_table >= 0)[:, :, None], kv_pos[idx], -1)
    pos_view = pos.reshape(b, maxb * bs)
    return ragged_verify_attention_ref(q, k_view, v_view, q_pos, pos_view,
                                       window=window)


def paged_ragged_verify_attention_quant_ref(
        q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
        k_scale: jax.Array, v_scale: jax.Array, block_table: jax.Array,
        q_pos: jax.Array, kv_pos: jax.Array,
        window: Optional[int] = None) -> jax.Array:
    """Oracle for the quantized block-paged kernel: gather the int8 view
    and its scales through the block table, dequantize in f32 (``int8 *
    scale``; the kernel applies the same scales to each slot's score and
    probability, equal up to f32 rounding), then run the dense oracle.

    pool_k/pool_v [N, BS, KV, D] int8; k_scale/v_scale [N, BS, KV] fp32;
    block_table [B, MAXB] (-1 = unallocated); kv_pos [N, BS]."""
    b, maxb = block_table.shape
    bs = pool_k.shape[1]
    idx = jnp.maximum(block_table, 0)
    k_view = (pool_k[idx].astype(jnp.float32)
              * k_scale[idx][..., None])
    v_view = (pool_v[idx].astype(jnp.float32)
              * v_scale[idx][..., None])
    k_view = k_view.reshape((b, maxb * bs) + k_view.shape[3:])
    v_view = v_view.reshape((b, maxb * bs) + v_view.shape[3:])
    pos = jnp.where((block_table >= 0)[:, :, None], kv_pos[idx], -1)
    pos_view = pos.reshape(b, maxb * bs)
    return ragged_verify_attention_ref(q, k_view, v_view, q_pos, pos_view,
                                       window=window)


def ngram_propose_ref(tokens: jax.Array, ctx_len: jax.Array, *, n: int,
                      k: int) -> Tuple[jax.Array, jax.Array]:
    """Oracle for the prompt-lookup suffix-match kernel.

    ``tokens [B, L]`` is each sequence's known text (committed history
    with the pending token appended); ``ctx_len [B]`` how many leading
    entries are real.  Finds the MOST RECENT earlier occurrence of the
    length-``n`` suffix ``tokens[ctx_len-n : ctx_len]`` and proposes the
    ``k`` tokens that followed it (clipped to the known text).

    Returns ``(proposed [B, K] int32 — zero-padded beyond count,
    count [B] int32 — 0 when no match)``.  Integer-exact: the Pallas
    kernel must match this bit for bit.
    """
    b, l = tokens.shape
    idx = jnp.arange(l)

    def one(row, c):
        # suffix values via masked reductions (no dynamic gather)
        match = jnp.ones((l,), bool)
        for j in range(n):
            sj = jnp.sum(jnp.where(idx == c - n + j, row, 0))
            # row[i + j] as a static shift, padded with -1 (never a token)
            shifted = jnp.concatenate(
                [row[j:], jnp.full((j,), -1, row.dtype)]) if j else row
            match = match & (shifted == sj)
        # a usable match needs >= 1 known continuation token (i + n <= c-1)
        # — which also excludes the trivial occurrence at i = c - n — and
        # enough context to have a length-n suffix at all
        match = match & (idx + n <= c - 1) & (c >= n + 1)
        best = jnp.max(jnp.where(match, idx, -1))
        found = best >= 0
        count = jnp.where(found,
                          jnp.minimum(jnp.int32(k), c - (best + n)),
                          0).astype(jnp.int32)
        outs = []
        for m in range(k):
            tm = jnp.sum(jnp.where(idx == best + n + m, row, 0))
            outs.append(jnp.where(m < count, tm, 0))
        prop = (jnp.stack(outs) if k else jnp.zeros((0,), row.dtype))
        return prop.astype(jnp.int32), count

    return jax.vmap(one)(tokens, ctx_len.astype(jnp.int32))


def kld_accept_ref(target_logits: jax.Array, draft_logits: jax.Array,
                   draft_tokens: jax.Array
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Oracle for the fused post-hoc signal kernel.

    Returns per [B,T]: (kld = KL(p_target||q_draft), entropy_q,
    p_target(token), q_draft(token))."""
    tl = target_logits.astype(jnp.float32)
    dl = draft_logits.astype(jnp.float32)
    lp = jax.nn.log_softmax(tl, axis=-1)
    lq = jax.nn.log_softmax(dl, axis=-1)
    p = jnp.exp(lp)
    q = jnp.exp(lq)
    kld = jnp.sum(p * (lp - lq), axis=-1)
    ent = -jnp.sum(q * lq, axis=-1)
    p_tok = jnp.take_along_axis(p, draft_tokens[..., None], axis=-1)[..., 0]
    q_tok = jnp.take_along_axis(q, draft_tokens[..., None], axis=-1)[..., 0]
    return kld, ent, p_tok, q_tok
