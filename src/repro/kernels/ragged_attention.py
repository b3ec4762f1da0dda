"""Pallas TPU kernel: ragged decode / verification attention.

This is the TPU-native replacement for the FlashAttention-2 varlen kernel
the paper integrates into vLLM's Target Worker (paper §3 / DESIGN.md §3):
requests with heterogeneous speculative lengths are scored in a single
batch pass.  On TPU the raggedness lives in *masks over a padded
[T = SL_cap+1] query block* — SL_cap bounds the pad waste, which is the
serendipitous synergy between the paper's straggler mitigation and MXU
tiling.

Three entry points share ONE online-softmax body (:func:`_sweep_step`)
and differ only in where a KV tile comes from: a dense per-sequence ring,
the block-paged pool (index maps dereference the scalar-prefetched block
table), or the int8 pool (same lookup plus in-register dequantization).

Layout as the kernels see it (the wrappers do every reshape)
------------------------------------------------------------
  q        [B, KV, G*T, D]    grouped-query view, row r = g*T + t
  k / v    [B, W, KV, D]      dense ring, or [N, BS, KV, D] pool
  kv_pos   [B, 1, W]          or [N, 1, BS]: slot positions (-1 empty)
  q_pos    [B, G*T, 1]        query positions already tiled to the rows
  out      [B, KV, G*T, D]

One grid step takes a KV tile with ALL its KV heads — block
``(1, BK, KV, D)`` keeps the last two dims whole, which is what the TPU
lowering accepts — and loops over the heads inside the kernel.  The
position operands carry a unit axis so their blocks' last two dims are
whole too, and the causal/window mask is a broadcast compare of
``[1, BK]`` slot positions against ``[G*T, 1]`` query positions (no
boolean tiling, which Mosaic cannot lower).  The (m, l, acc) state lives
in VMEM scratch across the innermost KV sweep (flash-decoding).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# f32 dots at f32 accuracy: a TPU's default precision rounds f32 operands
# to bf16 (about 1e-2 off the f32 oracle on a v5e), which breaks greedy
# speculative streams' agreement with the autoregressive stream
F32 = jax.lax.Precision.HIGHEST


def _sweep_step(q_ref, load_kv: Callable, kvp, qp, o_ref, m_ref, l_ref,
                acc_ref, *, step, nsteps: int, window: Optional[int],
                sm_scale: float):
    """One KV tile of the online-softmax sweep, for every KV head.

    ``load_kv(h)`` returns head ``h``'s f32 ``([BK, D], [BK, D])`` K/V
    tile; ``kvp [1, BK]`` and ``qp [G*T, 1]`` are the slot and query
    positions (an unusable slot already carries -1)."""

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mask = (kvp >= 0) & (kvp <= qp)                       # [G*T, BK]
    if window is not None:
        mask = mask & (qp - kvp < window)
    for h in range(q_ref.shape[1]):
        k, v = load_kv(h)
        q = q_ref[0, h].astype(jnp.float32)               # [G*T, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=F32,
                                preferred_element_type=jnp.float32)
        s = jnp.where(mask, s * sm_scale, NEG_INF)
        m_prev = m_ref[h]                                 # [G*T, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[h] = l_ref[h] * alpha + p.sum(-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=F32,
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(step == nsteps - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def _scratch(kv: int, rows: int, d: int):
    return [pltpu.VMEM((kv, rows, 1), jnp.float32),
            pltpu.VMEM((kv, rows, 1), jnp.float32),
            pltpu.VMEM((kv, rows, d), jnp.float32)]


def _kernel_view(q: jax.Array, q_pos: jax.Array, kv: int):
    """[B,T,H,D] queries -> the [B,KV,G*T,D] kernel view, and [B,T]
    positions tiled to its rows as [B,G*T,1]."""
    b, t, h, d = q.shape
    g = h // kv
    qr = q.reshape(b, t, kv, g, d).transpose(0, 2, 3, 1, 4)
    qp = jnp.tile(q_pos.astype(jnp.int32), (1, g))
    return qr.reshape(b, kv, g * t, d), qp.reshape(b, g * t, 1)


def _from_kernel_view(out: jax.Array, t: int) -> jax.Array:
    """Inverse of :func:`_kernel_view` for the output: [B,T,H,D]."""
    b, kv, gt, d = out.shape
    g = gt // t
    return (out.reshape(b, kv, g, t, d).transpose(0, 3, 1, 2, 4)
            .reshape(b, t, kv * g, d))


def _kernel(q_ref, k_ref, v_ref, kvp_ref, qp_ref, o_ref,
            m_ref, l_ref, acc_ref, *, window: Optional[int], nwb: int,
            sm_scale: float):
    def load_kv(h):
        return (k_ref[0, :, h, :].astype(jnp.float32),
                v_ref[0, :, h, :].astype(jnp.float32))

    _sweep_step(q_ref, load_kv, kvp_ref[0], qp_ref[0], o_ref, m_ref, l_ref,
                acc_ref, step=pl.program_id(1), nsteps=nwb, window=window,
                sm_scale=sm_scale)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def ragged_verify_attention(q: jax.Array, k_buf: jax.Array, v_buf: jax.Array,
                            q_pos: jax.Array, kv_pos: jax.Array, *,
                            window: Optional[int] = None,
                            block_k: int = 512,
                            interpret: bool = False) -> jax.Array:
    """q [B,T,H,D]; k_buf/v_buf [B,W,KV,D]; q_pos [B,T]; kv_pos [B,W].
    Returns [B,T,H,D].  Grid (B, W // BK); see module docstring."""
    b, t, h, d = q.shape
    w, kv = k_buf.shape[1], k_buf.shape[2]
    kv_pos = jnp.broadcast_to(kv_pos.astype(jnp.int32), (b, w))
    # the kv_pos tile's lane dim must be whole or a multiple of 128
    bk = w if w <= block_k else max(block_k // 128, 1) * 128
    if w % bk:
        pad = bk - w % bk
        k_buf = jnp.pad(k_buf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_buf = jnp.pad(v_buf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
        w += pad
    nwb = w // bk
    qr, qp = _kernel_view(q, q_pos, kv)
    gt = qr.shape[2]
    out = pl.pallas_call(
        functools.partial(_kernel, window=window, nwb=nwb,
                          sm_scale=1.0 / math.sqrt(d)),
        grid=(b, nwb),
        in_specs=[
            pl.BlockSpec((1, kv, gt, d), lambda bi, wi: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bk, kv, d), lambda bi, wi: (bi, wi, 0, 0)),
            pl.BlockSpec((1, bk, kv, d), lambda bi, wi: (bi, wi, 0, 0)),
            pl.BlockSpec((1, 1, bk), lambda bi, wi: (bi, 0, wi)),
            pl.BlockSpec((1, gt, 1), lambda bi, wi: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kv, gt, d), lambda bi, wi: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, gt, d), q.dtype),
        scratch_shapes=_scratch(kv, gt, d),
        interpret=interpret,
    )(qr, k_buf, v_buf, kv_pos.reshape(b, 1, w), qp)
    return _from_kernel_view(out, t)


# ---------------------------------------------------------------------------
# Block-paged variants: the KV sweep walks each sequence's block table and
# the index maps dereference it (scalar prefetch), so the kernel reads
# straight from the shared block pool — no per-sequence dense view is ever
# materialized (the XLA fallback in kernels/ref.py gathers instead).  The
# int8 variant streams the per-slot-per-KV-head fp32 amax scales through
# the same lookup and dequantizes in-register right before the dots, so
# the HBM bytes swept per round shrink to the int8 pool + scale footprint
# (DESIGN.md §13).
# ---------------------------------------------------------------------------


def _paged_kernel(bt_ref, q_ref, k_ref, v_ref, *rest, window: Optional[int],
                  nlb: int, sm_scale: float, quant: bool):
    if quant:
        ks_ref, vs_ref, kvp_ref, qp_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        kvp_ref, qp_ref, o_ref, m_ref, l_ref, acc_ref = rest
    lb = pl.program_id(1)

    def load_kv(h):
        k = k_ref[0, :, h, :].astype(jnp.float32)         # [BS, D]
        v = v_ref[0, :, h, :].astype(jnp.float32)
        if quant:   # int8 tile * fp32 per-slot scale column
            k = k * ks_ref[0, :, h:h + 1]
            v = v * vs_ref[0, :, h:h + 1]
        return k, v

    # unallocated logical block (-1): its clamped block-0 tile is fetched
    # but every slot is masked out
    entry = bt_ref[pl.program_id(0), lb]
    kvp = jnp.where(entry >= 0, kvp_ref[0], -1)           # [1, BS]
    _sweep_step(q_ref, load_kv, kvp, qp_ref[0], o_ref, m_ref, l_ref,
                acc_ref, step=lb, nsteps=nlb, window=window,
                sm_scale=sm_scale)


def _paged_call(q, pools, block_table, q_pos, kv_pos, *, window, interpret):
    """Shared pallas_call for the fp (``pools = (k, v)``) and int8
    (``pools = (k, v, k_scale, v_scale)``) paged kernels."""
    b, t, h, d = q.shape
    n, bs, kv = pools[0].shape[:3]
    maxb = block_table.shape[1]
    qr, qp = _kernel_view(q, q_pos, kv)
    gt = qr.shape[2]

    def blk(bi, li, bt):
        return jnp.maximum(bt[bi, li], 0)

    tile = pl.BlockSpec((1, bs, kv, d),
                        lambda bi, li, bt: (blk(bi, li, bt), 0, 0, 0))
    scale = pl.BlockSpec((1, bs, kv),
                         lambda bi, li, bt: (blk(bi, li, bt), 0, 0))
    quant = len(pools) == 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, maxb),
        in_specs=[
            pl.BlockSpec((1, kv, gt, d), lambda bi, li, bt: (bi, 0, 0, 0)),
            tile, tile, *([scale, scale] if quant else []),
            pl.BlockSpec((1, 1, bs),
                         lambda bi, li, bt: (blk(bi, li, bt), 0, 0)),
            pl.BlockSpec((1, gt, 1), lambda bi, li, bt: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kv, gt, d),
                               lambda bi, li, bt: (bi, 0, 0, 0)),
        scratch_shapes=_scratch(kv, gt, d),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, window=window, nlb=maxb,
                          sm_scale=1.0 / math.sqrt(d), quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, gt, d), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), qr, *pools,
      kv_pos.astype(jnp.int32).reshape(n, 1, bs), qp)
    return _from_kernel_view(out, t)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_ragged_verify_attention(q: jax.Array, pool_k: jax.Array,
                                  pool_v: jax.Array, block_table: jax.Array,
                                  q_pos: jax.Array, kv_pos: jax.Array, *,
                                  window: Optional[int] = None,
                                  interpret: bool = False) -> jax.Array:
    """Paged decode/verify attention straight off the shared block pool.

    q [B,T,H,D]; pool_k/pool_v [N, BS, KV, D]; block_table [B, MAXB]
    int32 (-1 = unallocated); q_pos [B,T]; kv_pos [N, BS] pool-level slot
    positions (-1 = empty).  Returns [B,T,H,D].

    Grid = (B, MAXB): the innermost sweep visits one *logical* block per
    step and the k/v/kv_pos index maps look its physical id up in the
    scalar-prefetched table (clamped to 0 for unallocated entries, whose
    scores are then fully masked).  One BS-token tile per step is the
    clarity-first schedule; the production knob is fetching several
    table entries per step so the score tile reaches MXU width.
    """
    return _paged_call(q, (pool_k, pool_v), block_table, q_pos, kv_pos,
                       window=window, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_ragged_verify_attention_quant(
        q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
        k_scale: jax.Array, v_scale: jax.Array, block_table: jax.Array,
        q_pos: jax.Array, kv_pos: jax.Array, *,
        window: Optional[int] = None,
        interpret: bool = False) -> jax.Array:
    """Paged decode/verify attention off the int8 block pool.

    q [B,T,H,D]; pool_k/pool_v [N, BS, KV, D] int8;
    k_scale/v_scale [N, BS, KV] fp32 amax scales; block_table [B, MAXB]
    int32 (-1 = unallocated); q_pos [B,T]; kv_pos [N, BS].  Returns
    [B,T,H,D].

    Same grid and online-softmax body as
    :func:`paged_ragged_verify_attention`; the scale tiles ride the same
    scalar-prefetched table lookup as the kv_pos tile, so unallocated
    entries clamp to block 0 and mask out identically.
    """
    return _paged_call(q, (pool_k, pool_v, k_scale, v_scale), block_table,
                       q_pos, kv_pos, window=window, interpret=interpret)
