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
the block-paged pool (pages copied out of HBM through the
scalar-prefetched block table), or the int8 pool (the same copies, with
the per-slot scales applied to the scores and probabilities).

Layout as the kernels see it (the wrappers do every reshape)
------------------------------------------------------------
  q        [B, KV, G*T, D]    grouped-query view, row r = g*T + t
  k / v    [B, W, KV, D]      dense ring, or [N, BS, lanes] pool pages:
                              a slot's KV heads side by side, padded to
                              a multiple of 128 lanes
  kv_pos   [B, 1, W]          or [B, NC, 1, P*BS]: each row's slot
                              positions, chunk by chunk (-1 empty)
  q_pos    [B, G*T, 1]        query positions already tiled to the rows
  out      [B, KV, G*T, D]

The dense kernel's grid step takes a KV tile with ALL its KV heads —
block ``(1, BK, KV, D)`` keeps the last two dims whole, which is what the
TPU lowering accepts — and loops over the heads inside the kernel.  The
paged kernel takes one row a grid step and loops over the live chunks of
its table, ``P = max(1, 128 // BS)`` pages a chunk, so a score tile is
one MXU width at BS 16 (:func:`paged_ragged_verify_attention`).  The
position operands carry a unit axis so their blocks' last two dims are
whole too, and the causal/window mask is a broadcast compare of
``[1, BK]`` slot positions against ``[G*T, 1]`` query positions (no
boolean tiling, which Mosaic cannot lower).  The (m, l, acc) state lives
in VMEM scratch across the KV sweep (flash-decoding).
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


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _sweep_step(q_ref, load_kv: Callable, kvp, qp, m_ref, l_ref, acc_ref, *,
                window: Optional[int], sm_scale: float,
                scales: Optional[Callable] = None):
    """One KV tile of the online-softmax sweep, for every KV head.

    ``load_kv(h)`` returns head ``h``'s f32 ``([BK, D], [BK, D])`` K/V
    tile; ``kvp [1, BK]`` and ``qp [G*T, 1]`` are the slot and query
    positions (an unusable slot already carries -1).  ``scales(h)``,
    where given, returns head ``h``'s per-slot ``([1, BK], [1, BK])`` K/V
    dequantization scales: a K scale multiplies its slot's score column
    and a V scale its slot's probability, which is the same product as
    scaling the tiles.  A fully masked tile changes nothing (``p = 0``,
    ``alpha = 1``)."""
    mask = (kvp >= 0) & (kvp <= qp)                       # [G*T, BK]
    if window is not None:
        mask = mask & (qp - kvp < window)
    for h in range(q_ref.shape[1]):
        k, v = load_kv(h)
        q = q_ref[0, h].astype(jnp.float32)               # [G*T, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=F32,
                                preferred_element_type=jnp.float32)
        if scales is not None:
            k_scale, v_scale = scales(h)
            s = s * k_scale
        s = jnp.where(mask, s * sm_scale, NEG_INF)
        m_prev = m_ref[h]                                 # [G*T, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[h] = l_ref[h] * alpha + p.sum(-1, keepdims=True)
        pv = p if scales is None else p * v_scale
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())), precision=F32,
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _finalize(o_ref, l_ref, acc_ref):
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

    step = pl.program_id(1)
    pl.when(step == 0)(lambda: _init_state(m_ref, l_ref, acc_ref))
    _sweep_step(q_ref, load_kv, kvp_ref[0], qp_ref[0], m_ref, l_ref,
                acc_ref, window=window, sm_scale=sm_scale)
    pl.when(step == nwb - 1)(lambda: _finalize(o_ref, l_ref, acc_ref))


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
# Block-paged variants: the kernel reads straight from the shared block
# pool, which stays in HBM, through each sequence's scalar-prefetched block
# table; no per-sequence dense view of the pool is ever materialized (the
# XLA fallback in kernels/ref.py gathers instead).  The int8 variant copies
# int8 pages the same way; each slot's per-KV-head fp32 amax scales arrive
# with the row's slot positions and scale its scores and probabilities, so
# the HBM bytes swept per round shrink to the int8 pool + scale footprint
# (DESIGN.md §13).
# ---------------------------------------------------------------------------


def live_blocks(last_pos, block_size: int, max_blocks: int):
    """How many leading logical blocks a row's paged sweep reads: every
    block up to the one holding its last query position ``last_pos``, at
    most ``max_blocks`` (0 for a negative position).  Elementwise over
    NumPy or JAX integer arrays.

    Position ``p`` is stored in logical block ``p // block_size``, so a
    later block holds only positions past every query, all causally
    masked: leaving it out changes no result."""
    return (last_pos // block_size + 1).clip(0, max_blocks)


def _paged_kernel(bt_ref, lo_ref, hi_ref, q_ref, kvp_ref, qp_ref, *rest,
                  window: Optional[int], sm_scale: float, d: int,
                  pages: int, quant: bool):
    if quant:
        sc_ref, *rest = rest
    k_hbm, v_hbm, o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf, sem = rest
    b = pl.program_id(0)
    maxb, kv = bt_ref.shape[1], q_ref.shape[1]
    lo, hi = lo_ref[b], hi_ref[b]        # live logical blocks [lo, hi)
    c_lo, c_hi = lo // pages, (hi + pages - 1) // pages

    def pages_of(c, slot):
        """(fetched?, its K and V copies, its V buffer) for each page of
        chunk ``c``, bound for buffer ``slot``."""
        for i in range(pages):
            j = c * pages + i
            entry = bt_ref[b, jnp.minimum(j, maxb - 1)]
            fetch = (j >= lo) & (j < hi) & (entry >= 0)
            src = jnp.maximum(entry, 0)
            copies = [pltpu.make_async_copy(hbm.at[src], buf.at[slot, i],
                                            sem.at[n, slot])
                      for n, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                      (v_hbm, v_buf)))]
            yield fetch, copies, v_buf.at[slot, i]

    def start(c, slot):
        for fetch, copies, v_page in pages_of(c, slot):
            @pl.when(fetch)
            def _():
                for cp in copies:
                    cp.start()

            # a page that is not fetched is masked, but its V rows still
            # meet p = 0 in the dot: zero them, so that stale VMEM can
            # never bring a NaN in
            @pl.when(jnp.logical_not(fetch))
            def _():
                v_page[...] = jnp.zeros(v_page.shape, v_page.dtype)

    def wait(c, slot):
        for fetch, copies, _ in pages_of(c, slot):
            @pl.when(fetch)
            def _():
                for cp in copies:
                    cp.wait()

    def chunk(c, carry):
        slot = (c - c_lo) % 2

        @pl.when(c + 1 < c_hi)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        # [pages, BS, lanes] -> [pages * BS, lanes]: one row per slot, the
        # KV heads side by side along the lanes
        k = k_buf[slot].astype(jnp.float32).reshape(-1, k_buf.shape[-1])
        v = v_buf[slot].astype(jnp.float32).reshape(-1, v_buf.shape[-1])

        def load_kv(h):
            return k[:, h * d:(h + 1) * d], v[:, h * d:(h + 1) * d]

        def scales(h):   # the int8 pool's per-slot fp32 scales
            return sc_ref[0, c, h:h + 1], sc_ref[0, c, kv + h:kv + h + 1]

        _sweep_step(q_ref, load_kv, kvp_ref[0, c], qp_ref[0], m_ref, l_ref,
                    acc_ref, window=window, sm_scale=sm_scale,
                    scales=scales if quant else None)
        return carry

    _init_state(m_ref, l_ref, acc_ref)
    pl.when(c_lo < c_hi)(lambda: start(c_lo, 0))
    jax.lax.fori_loop(c_lo, c_hi, chunk, 0)
    _finalize(o_ref, l_ref, acc_ref)


def _slot_rows(pool: jax.Array) -> jax.Array:
    """[N, BS, KV, D] -> [N, BS, lanes]: each slot's KV heads side by side,
    zero-padded to a multiple of 128 lanes, the shape in which a page
    can be copied out of HBM whole."""
    n, bs, kv, d = pool.shape
    lanes = pl.cdiv(kv * d, 128) * 128
    return jnp.pad(pool.reshape(n, bs, kv * d),
                   ((0, 0), (0, 0), (0, lanes - kv * d)))


def _paged_call(q, pools, block_table, q_pos, kv_pos, *, window, interpret):
    """Shared pallas_call for the fp (``pools = (k, v)``) and int8
    (``pools = (k, v, k_scale, v_scale)``) paged kernels."""
    b, t, h, d = q.shape
    n, bs, kv = pools[0].shape[:3]
    maxb = block_table.shape[1]
    pages = max(1, 128 // bs)            # table entries per chunk
    nc = pl.cdiv(maxb, pages)
    table = block_table.astype(jnp.int32)
    q_pos = q_pos.astype(jnp.int32)
    qr, qp = _kernel_view(q, q_pos, kv)
    gt = qr.shape[2]
    # each row's live extent [lo, hi) in logical blocks: nothing past its
    # last query position, nothing wholly before its window
    hi = live_blocks(q_pos.max(axis=1), bs, maxb)
    lo = (jnp.zeros_like(hi) if window is None else
          ((q_pos.min(axis=1) - window + 1) // bs).clip(0, maxb))
    col = jnp.arange(maxb)[None]
    swept = (table >= 0) & (col >= lo[:, None]) & (col < hi[:, None])
    idx = jnp.maximum(table, 0)

    def chunk_rows(x, fill):
        """[B, MAXB, BS, C] per-slot values of the swept blocks (``fill``
        elsewhere) -> [B, NC, C, pages * BS], a chunk's slots on lanes."""
        x = jnp.where(swept[:, :, None, None], x, fill)
        x = jnp.pad(x, ((0, 0), (0, nc * pages - maxb), (0, 0), (0, 0)),
                    constant_values=fill)
        return x.reshape(b, nc, pages * bs, -1).transpose(0, 1, 3, 2)

    per_slot = [chunk_rows(kv_pos.astype(jnp.int32)[idx][..., None], -1)]
    if len(pools) == 4:
        per_slot.append(jnp.concatenate([chunk_rows(s[idx], 0.0)
                                         for s in pools[2:]], axis=2))

    def row(bi, *_):
        return (bi, 0, 0, 0)

    k_rows, v_rows = _slot_rows(pools[0]), _slot_rows(pools[1])
    page = (2, pages) + k_rows.shape[1:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kv, gt, d), row),
            pl.BlockSpec((1, nc, 1, pages * bs), row),
            pl.BlockSpec((1, gt, 1), lambda bi, *_: (bi, 0, 0)),
            *[pl.BlockSpec((1, nc, 2 * kv, pages * bs), row)]
            * (len(per_slot) - 1),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kv, gt, d), row),
        scratch_shapes=[
            *_scratch(kv, gt, d),
            pltpu.VMEM(page, k_rows.dtype), pltpu.VMEM(page, v_rows.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, window=window,
                          sm_scale=1.0 / math.sqrt(d), d=d, pages=pages,
                          quant=len(pools) == 4),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, gt, d), q.dtype),
        interpret=interpret,
    )(table, lo, hi, qr, per_slot[0], qp, *per_slot[1:], k_rows, v_rows)
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
    positions (-1 = empty).  Returns [B,T,H,D].  A slot of a row's
    logical block ``j`` holds a position in ``[j*BS, (j+1)*BS)`` or -1,
    as the pool's writes leave it (:mod:`repro.models.cache`).

    Grid = (B,): one step per row, which sweeps only the row's live
    extent of its table — the blocks up to its last query position
    (:func:`live_blocks`) and, with a window, from the first block its
    earliest query can still see.  The extent is scalar-prefetched with
    the table.  The sweep takes ``P = max(1, 128 // BS)`` table entries
    per chunk, so the score tile is ``[G*T, P*BS]``, one MXU width at BS
    16; each live, allocated page is copied from the HBM pool into VMEM,
    the next chunk's copies in flight while this chunk's dots run.  Pages
    outside the extent or unallocated are neither copied nor computed on
    as scores: their slots are masked.  A row with no live block is
    finalized to zeros.
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

    Same schedule and online-softmax body as
    :func:`paged_ragged_verify_attention`: the int8 pages are copied
    through the same table lookup, and each swept slot's scales arrive
    beside its position, gathered through the table in the wrapper.
    """
    return _paged_call(q, (pool_k, pool_v, k_scale, v_scale), block_table,
                       q_pos, kv_pos, window=window, interpret=interpret)
