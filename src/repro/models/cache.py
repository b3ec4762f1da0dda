"""Decode-time caches: ring-buffer / block-paged KV + recurrent state.

One cache pytree per model instance.  Common fields:

* ``length [B]``   — number of tokens whose KV/state is *committed*.
* ``kv_pos`` — absolute sequence index stored in each KV slot
  (-1 = never written).  Validity of a slot for a query at position ``q`` is
  ``0 <= kv_pos <= q`` (and ``q - kv_pos < window`` for windowed layers).
  Rollback after speculative verification is therefore *free* for KV layers:
  resetting ``length`` masks the stale slots (see DESIGN.md §4).

Two physical KV layouts share those semantics:

* **dense ring** (``kv_pos [B, W]``) — one W-wide row per batch slot,
  slot = pos % W.  The ring makes windowed caches O(window) instead of
  O(seq): ``long_500k`` decode for SWA/hybrid archs holds a 2–4k ring,
  not a 524k buffer.  Correctness requires window >> SL_max so one
  speculation round can never wrap past its own rollback horizon.
* **block-paged pool** (``paged_cache_struct``) — a shared pool
  ``[L, n_blocks, block_size, KV, D]`` plus per-sequence block tables
  ``[B, max_blocks]`` (-1 = unallocated) and pool-level
  ``kv_pos [n_blocks, block_size]``.  Position ``p`` of sequence ``b``
  lives at physical slot ``block_table[b, p // bs] * bs + p % bs`` — a
  *stable* mapping while the blocks stay allocated, so the dense
  overwrite-or-mask rollback argument carries over unchanged and commit
  stays pure length arithmetic.  Writes through an unallocated table
  entry are dropped; the serving-side allocator grows tables on demand
  and resets ``kv_pos`` of a block to -1 on (re)allocation so a block
  recycled from another sequence can never leak stale-but-causally-valid
  entries.  SSM / RG-LRU recurrent state is O(1) per sequence and stays
  dense per-slot in both layouts.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.models.ssm import ssm_dims

CacheT = Dict[str, Any]


# extra ring slots beyond the attention window: a T-token decode/verify
# call writes T new entries before the first query reads — without slack it
# would overwrite the oldest still-in-window keys (SL_max+1 = 11 < 16)
RING_SLACK = 16


def _kv_window(cfg: ModelConfig, max_len: int) -> int:
    if cfg.attention_window is not None:
        return min(max_len, cfg.attention_window + RING_SLACK)
    return max_len


def _local_window(cfg: ModelConfig, max_len: int) -> int:
    return min(max_len, cfg.rglru.local_attention_window + RING_SLACK)


def eff_kv_heads(cfg: ModelConfig) -> int:
    return cfg.kv_head_pad or cfg.num_kv_heads


def kv_buf_shape(cfg: ModelConfig, batch: int, window: int,
                 layers: int) -> Tuple[int, ...]:
    return (layers, batch, window, eff_kv_heads(cfg),
            cfg.resolved_head_dim)


def cache_struct(cfg: ModelConfig, batch: int, max_len: int,
                 dtype=jnp.bfloat16, enc_len: Optional[int] = None,
                 abstract: bool = False) -> CacheT:
    """Build the cache pytree (zeros) or its ShapeDtypeStruct skeleton."""

    def mk(shape, dt):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)

    def mk_pos(shape):
        if abstract:
            return jax.ShapeDtypeStruct(shape, jnp.int32)
        return jnp.full(shape, -1, jnp.int32)

    c: CacheT = {"length": mk((batch,), jnp.int32)}
    fam = cfg.family

    if fam in ("dense", "moe", "vlm"):
        w = _kv_window(cfg, max_len)
        c["k"] = mk(kv_buf_shape(cfg, batch, w, cfg.num_layers), dtype)
        c["v"] = mk(kv_buf_shape(cfg, batch, w, cfg.num_layers), dtype)
        c["kv_pos"] = mk_pos((batch, w))
    elif fam == "ssm":
        di, h, dc, n = ssm_dims(cfg)
        p = cfg.ssm.head_dim
        c["ssd"] = mk((cfg.num_layers, batch, h, p, n), jnp.float32)
        c["conv"] = mk((cfg.num_layers, batch, cfg.ssm.conv_width - 1, dc), dtype)
    elif fam == "hybrid":
        w = _local_window(cfg, max_len)
        n_attn, n_rec = hybrid_layer_counts(cfg)
        c["k"] = mk(kv_buf_shape(cfg, batch, w, n_attn), dtype)
        c["v"] = mk(kv_buf_shape(cfg, batch, w, n_attn), dtype)
        c["kv_pos"] = mk_pos((batch, w))
        c["lru"] = mk((n_rec, batch, cfg.rglru.lru_width), jnp.float32)
        c["conv"] = mk((n_rec, batch, cfg.rglru.conv_width - 1,
                        cfg.rglru.lru_width), dtype)
    elif fam == "audio":
        w = max_len
        c["k"] = mk(kv_buf_shape(cfg, batch, w, cfg.num_layers), dtype)
        c["v"] = mk(kv_buf_shape(cfg, batch, w, cfg.num_layers), dtype)
        c["kv_pos"] = mk_pos((batch, w))
        se = enc_len if enc_len is not None else 1
        c["cross_k"] = mk(kv_buf_shape(cfg, batch, se, cfg.num_layers), dtype)
        c["cross_v"] = mk(kv_buf_shape(cfg, batch, se, cfg.num_layers), dtype)
        c["enc_valid"] = mk((batch, se), jnp.bool_)
    else:
        raise ValueError(f"unknown family {fam}")
    return c


def hybrid_layer_is_attention(cfg: ModelConfig, i: int) -> bool:
    """RecurrentGemma 1:2 pattern — (rec, rec, attn) repeating."""
    return i % (cfg.rglru.blocks_per_attention + 1) == cfg.rglru.blocks_per_attention


def hybrid_layer_counts(cfg: ModelConfig) -> Tuple[int, int]:
    """(n_attention, n_recurrent) layers of a hybrid stack — the single
    source for every cache builder's layer-axis sizes."""
    n_attn = sum(1 for i in range(cfg.num_layers)
                 if hybrid_layer_is_attention(cfg, i))
    return n_attn, cfg.num_layers - n_attn


def cache_window(cache: CacheT) -> int:
    return cache["kv_pos"].shape[-1]


# ---------------------------------------------------------------------------
# int8 quantized KV storage (kv_quant="int8", DESIGN.md §4 / §13)
# ---------------------------------------------------------------------------

KV_QUANT_MODES = ("none", "int8")
INT8_QMAX = 127.0


def is_quantized(cache: CacheT) -> bool:
    return "k_scale" in cache


def supports_kv_quant(cfg: ModelConfig) -> bool:
    """Quantized storage rides the block pool; the hybrid family is
    excluded (its recurrent rows are fp per-slot state and the grouped
    layer-axis cache threading is not worth the extra plumbing)."""
    return cfg.family in ("dense", "moe", "vlm")


def paged_kv_layers(cfg: ModelConfig) -> int:
    """Layer-axis size of the paged K/V pools for this family."""
    if cfg.family == "hybrid":
        return hybrid_layer_counts(cfg)[0]
    return cfg.num_layers


def scale_buf_shape(cfg: ModelConfig, num_blocks: int, block_size: int,
                    layers: int) -> Tuple[int, ...]:
    """Per-slot-per-KV-head fp32 amax scales: one scale per stored KV
    vector.  Slot granularity (not per-block) because decode writes land
    one token at a time through the table — requantizing a whole block
    would need a read-modify-write of its other slots."""
    return (layers, num_blocks, block_size, eff_kv_heads(cfg))


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[..., KV, D] fp -> (int8 values, fp32 per-[..., KV] amax scales).

    All math in f32 with round-half-even, so every producer (multi-row
    prefill, tail prefill, decode/verify writes) quantizes the same
    vector bit-identically — the warm-vs-cold stream-identity anchor.
    A zero vector maps to scale 1.0 (not 0) so dequant never divides or
    multiplies by zero-by-convention."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / INT8_QMAX, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -INT8_QMAX, INT8_QMAX)
    return q.astype(jnp.int8), scale


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_kv`: [..., KV, D] int8 + [..., KV]
    scales -> f32.  The Pallas kv-sweep applies the same scales
    in-register, to each slot's score and probability; this jnp form is
    the oracle's and the XLA fallback's."""
    return q.astype(jnp.float32) * scale[..., None]


def fake_quantize_kv(x: jax.Array) -> jax.Array:
    """dequantize(quantize(x)) at x's dtype — what attention must read
    during prefill so cold-prefill, warm-tail and decode paths all see
    the identical (quantized) KV values."""
    q, s = quantize_kv(x)
    return dequantize_kv(q, s).astype(x.dtype)


def kv_block_bytes(cfg: ModelConfig, block_size: int, kv_quant: str,
                   dtype=jnp.float32) -> int:
    """HBM bytes one pool block costs across all paged layers (K + V,
    plus the scale arrays under int8).  The scheduler's byte accounting
    and the equal-byte pool sizing both resolve through here."""
    layers = paged_kv_layers(cfg)
    elems = layers * block_size * eff_kv_heads(cfg) * cfg.resolved_head_dim
    if kv_quant == "int8":
        scales = layers * block_size * eff_kv_heads(cfg)
        return 2 * (elems * 1 + scales * 4)
    if kv_quant == "none":
        return 2 * elems * jnp.dtype(dtype).itemsize
    raise ValueError(f"unknown kv_quant mode {kv_quant!r}")


def equal_byte_blocks(cfg: ModelConfig, fp_blocks: int, block_size: int,
                      fp_dtype=jnp.float32) -> int:
    """How many int8 blocks the byte budget of ``fp_blocks`` fp blocks
    buys (>= 2x for any head_dim >= 8/3: int8 costs D + 4 bytes per
    stored vector vs 4*D fp32)."""
    fp = kv_block_bytes(cfg, block_size, "none", dtype=fp_dtype)
    q8 = kv_block_bytes(cfg, block_size, "int8")
    return fp_blocks * fp // q8


# ---------------------------------------------------------------------------
# Block-paged layout
# ---------------------------------------------------------------------------

def supports_paged(cfg: ModelConfig) -> bool:
    """Families whose attention KV can live in the shared block pool.
    SSM is attention-free; audio's cross-KV is per-request encoder state."""
    return cfg.family in ("dense", "moe", "vlm", "hybrid")


def is_paged(cache: CacheT) -> bool:
    return "block_table" in cache


def max_blocks_per_seq(max_len: int, block_size: int) -> int:
    return -(-max_len // block_size)


def pool_buf_shape(cfg: ModelConfig, num_blocks: int, block_size: int,
                   layers: int) -> Tuple[int, ...]:
    return (layers, num_blocks, block_size, eff_kv_heads(cfg),
            cfg.resolved_head_dim)


def paged_cache_struct(cfg: ModelConfig, batch: int, max_len: int,
                       num_blocks: int, block_size: int,
                       dtype=jnp.bfloat16, abstract: bool = False,
                       require_full_seq: bool = True,
                       kv_quant: str = "none") -> CacheT:
    """Block-paged cache pytree: shared KV pool + per-sequence tables.

    ``k``/``v`` are pools ``[L, n_blocks, bs, KV, D]`` (the same leading
    layer axis the dense layout scans over), ``kv_pos [n_blocks, bs]`` is
    pool-level, ``block_table [B, max_blocks]`` maps logical to physical
    blocks (-1 = unallocated).  Recurrent state (hybrid lru/conv) stays
    dense per-slot.

    ``kv_quant="int8"`` stores the pools as int8 and adds fp32 amax
    scale arrays ``k_scale``/``v_scale`` ``[L, n_blocks, bs, KV]`` —
    one scale per stored KV vector, written alongside the values and
    carried with the block through COW copies and eviction/revival.

    ``require_full_seq`` asserts the pool holds at least one max-length
    sequence — the LIFO-preemption convergence guarantee.  Prefix-cached
    serving relaxes it (DESIGN.md §12): the scheduler's coverage-aware
    pool-feasibility check owns convergence there, and the data plane
    itself only needs drop-semantics, which hold for any pool size.
    """
    if not supports_paged(cfg):
        raise ValueError(f"family {cfg.family!r} has no paged KV layout")
    if kv_quant not in KV_QUANT_MODES:
        raise ValueError(f"unknown kv_quant mode {kv_quant!r}")
    if kv_quant != "none" and not supports_kv_quant(cfg):
        raise ValueError(
            f"family {cfg.family!r} has no quantized KV layout")
    assert not require_full_seq or num_blocks * block_size >= max_len, (
        "pool smaller than one max-length sequence: "
        f"{num_blocks}x{block_size} < {max_len}")

    def mk(shape, dt):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)

    def mk_neg(shape):
        if abstract:
            return jax.ShapeDtypeStruct(shape, jnp.int32)
        return jnp.full(shape, -1, jnp.int32)

    maxb = max_blocks_per_seq(max_len, block_size)
    c: CacheT = {"length": mk((batch,), jnp.int32),
                 "kv_pos": mk_neg((num_blocks, block_size)),
                 "block_table": mk_neg((batch, maxb))}
    if cfg.family == "hybrid":
        n_attn, n_rec = hybrid_layer_counts(cfg)
        c["k"] = mk(pool_buf_shape(cfg, num_blocks, block_size, n_attn), dtype)
        c["v"] = mk(pool_buf_shape(cfg, num_blocks, block_size, n_attn), dtype)
        c["lru"] = mk((n_rec, batch, cfg.rglru.lru_width), jnp.float32)
        c["conv"] = mk((n_rec, batch, cfg.rglru.conv_width - 1,
                        cfg.rglru.lru_width), dtype)
    else:
        pool_dtype = jnp.int8 if kv_quant == "int8" else dtype
        c["k"] = mk(pool_buf_shape(cfg, num_blocks, block_size,
                                   cfg.num_layers), pool_dtype)
        c["v"] = mk(pool_buf_shape(cfg, num_blocks, block_size,
                                   cfg.num_layers), pool_dtype)
        if kv_quant == "int8":
            sshape = scale_buf_shape(cfg, num_blocks, block_size,
                                     cfg.num_layers)
            c["k_scale"] = mk(sshape, jnp.float32)
            c["v_scale"] = mk(sshape, jnp.float32)
    return c


def paged_prefill_view(cfg: ModelConfig, pool_k: jax.Array,
                       pool_v: jax.Array, kv_pos: jax.Array,
                       table_rows: jax.Array,
                       lengths: Optional[jax.Array] = None,
                       k_scale: Optional[jax.Array] = None,
                       v_scale: Optional[jax.Array] = None) -> CacheT:
    """Batch-R paged cache view over the *shared* pools, for prefilling a
    group of requests straight into their allocated blocks in ONE
    multi-row program (``table_rows [R, max_blocks]``, one row per
    request): pool-shaped leaves alias the live pools and every row's KV
    writes route through its own block-table row, so the rows land in
    disjoint blocks; per-sequence leaves (length, block table, hybrid
    recurrent rows) are fresh batch-R rows the engine scatters back into
    the batched cache afterwards.

    ``lengths [R]`` presets the committed length per row (zeros when
    omitted).  The prefix-cache tail prefill uses it to start a row at
    its cached-coverage offset, so decode-mode positions and attention
    see the shared prefix blocks as already-committed KV."""
    rows = table_rows.shape[0]
    length = (jnp.zeros((rows,), jnp.int32) if lengths is None
              else lengths.astype(jnp.int32))
    c: CacheT = {"length": length,
                 "k": pool_k, "v": pool_v, "kv_pos": kv_pos,
                 "block_table": table_rows}
    if k_scale is not None:
        c["k_scale"], c["v_scale"] = k_scale, v_scale
    if cfg.family == "hybrid":
        _, n_rec = hybrid_layer_counts(cfg)
        c["lru"] = jnp.zeros((n_rec, rows, cfg.rglru.lru_width), jnp.float32)
        c["conv"] = jnp.zeros((n_rec, rows, cfg.rglru.conv_width - 1,
                               cfg.rglru.lru_width), pool_k.dtype)
    return c


def _paged_flat_index(positions: jax.Array, block_table: jax.Array,
                      block_size: int, num_blocks: int,
                      keep: Optional[jax.Array]) -> jax.Array:
    """[B,T] positions -> flat pool slot via the table; out-of-range,
    unallocated, or ``~keep`` entries map past the pool (scatter-dropped)."""
    maxb = block_table.shape[1]
    blk = positions // block_size
    phys = jnp.take_along_axis(block_table, jnp.clip(blk, 0, maxb - 1),
                               axis=1)
    ok = (positions >= 0) & (blk < maxb) & (phys >= 0)
    if keep is not None:
        ok = ok & keep
    return jnp.where(ok, phys * block_size + positions % block_size,
                     num_blocks * block_size)


def write_kv_paged(pool_k: jax.Array, pool_v: jax.Array, k_new: jax.Array,
                   v_new: jax.Array, positions: jax.Array,
                   block_table: jax.Array,
                   keep: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Scatter [B,T,KV,D] new KV through the block table into the pool
    ``[N, bs, KV, D]``.  Writes to unallocated table entries — and, when
    ``keep [B,T]`` is given, masked positions — are dropped, which is what
    lets the verification pass of a short-SL sequence stay inside its own
    block budget while the batch runs a wider bucket."""
    n, bs = pool_k.shape[:2]
    flat = _paged_flat_index(positions, block_table, bs, n, keep).reshape(-1)
    fk = pool_k.reshape((n * bs,) + pool_k.shape[2:])
    fv = pool_v.reshape((n * bs,) + pool_v.shape[2:])
    kf = k_new.reshape((-1,) + k_new.shape[2:]).astype(pool_k.dtype)
    vf = v_new.reshape((-1,) + v_new.shape[2:]).astype(pool_v.dtype)
    fk = fk.at[flat].set(kf, mode="drop")
    fv = fv.at[flat].set(vf, mode="drop")
    return fk.reshape(pool_k.shape), fv.reshape(pool_v.shape)


def write_pos_paged(kv_pos: jax.Array, positions: jax.Array,
                    block_table: jax.Array,
                    valid: Optional[jax.Array] = None,
                    keep: Optional[jax.Array] = None) -> jax.Array:
    """Update the pool-level slot-position map (once per model call).
    ``valid`` marks entries written as -1 (ragged prefill padding, dense
    ``write_pos`` semantics); ``keep`` drops the write entirely (decode
    write masking)."""
    n, bs = kv_pos.shape
    flat = _paged_flat_index(positions, block_table, bs, n, keep).reshape(-1)
    newpos = positions if valid is None else jnp.where(valid, positions, -1)
    return kv_pos.reshape(-1).at[flat].set(
        newpos.reshape(-1), mode="drop").reshape(kv_pos.shape)


def gather_paged_kv(pool_k: jax.Array, pool_v: jax.Array,
                    block_table: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-sequence dense views [B, max_blocks*bs, KV, D] of the pool.

    XLA reference path: functionally exact but materializes the view —
    the TPU data plane reads through the table inside the Pallas kernel
    instead (:func:`repro.kernels.ragged_attention
    .paged_ragged_verify_attention`).  Unallocated entries gather block 0;
    they are masked by the -1 entries of :func:`gather_paged_pos`."""
    idx = jnp.maximum(block_table, 0)
    b, maxb = block_table.shape
    bs = pool_k.shape[1]
    k = pool_k[idx].reshape((b, maxb * bs) + pool_k.shape[2:])
    v = pool_v[idx].reshape((b, maxb * bs) + pool_v.shape[2:])
    return k, v


def gather_paged_pos(kv_pos: jax.Array, block_table: jax.Array) -> jax.Array:
    """Per-sequence [B, max_blocks*bs] view of the pool-level kv_pos;
    unallocated table entries read as -1 (never valid)."""
    g = kv_pos[jnp.maximum(block_table, 0)]              # [B, MAXB, bs]
    g = jnp.where((block_table >= 0)[:, :, None], g, -1)
    return g.reshape(block_table.shape[0], -1)


def write_kv_paged_quant(pool_k: jax.Array, pool_v: jax.Array,
                         k_scale: jax.Array, v_scale: jax.Array,
                         k_new: jax.Array, v_new: jax.Array,
                         positions: jax.Array, block_table: jax.Array,
                         keep: Optional[jax.Array] = None
                         ) -> Tuple[jax.Array, jax.Array,
                                    jax.Array, jax.Array]:
    """Quantize-on-write: the int8 values and their fp32 scales scatter
    through the same flat pool index, so a dropped value write drops its
    scale too.  Per-layer pools ``[N, bs, KV, D]`` + scales
    ``[N, bs, KV]`` (the transformer scan slices the layer axis)."""
    n, bs = pool_k.shape[:2]
    flat = _paged_flat_index(positions, block_table, bs, n, keep).reshape(-1)
    qk, sk = quantize_kv(k_new)
    qv, sv = quantize_kv(v_new)
    fk = pool_k.reshape((n * bs,) + pool_k.shape[2:])
    fv = pool_v.reshape((n * bs,) + pool_v.shape[2:])
    fks = k_scale.reshape((n * bs,) + k_scale.shape[2:])
    fvs = v_scale.reshape((n * bs,) + v_scale.shape[2:])
    fk = fk.at[flat].set(qk.reshape((-1,) + qk.shape[2:]), mode="drop")
    fv = fv.at[flat].set(qv.reshape((-1,) + qv.shape[2:]), mode="drop")
    fks = fks.at[flat].set(sk.reshape((-1,) + sk.shape[2:]), mode="drop")
    fvs = fvs.at[flat].set(sv.reshape((-1,) + sv.shape[2:]), mode="drop")
    return (fk.reshape(pool_k.shape), fv.reshape(pool_v.shape),
            fks.reshape(k_scale.shape), fvs.reshape(v_scale.shape))


def gather_paged_kv_quant(pool_k: jax.Array, pool_v: jax.Array,
                          k_scale: jax.Array, v_scale: jax.Array,
                          block_table: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
    """Dequantized per-sequence dense views [B, max_blocks*bs, KV, D]
    (f32).  XLA reference path only — the TPU data plane dequantizes
    in-register inside the Pallas kv-sweep instead
    (:func:`repro.kernels.ragged_attention
    .paged_ragged_verify_attention_quant`)."""
    idx = jnp.maximum(block_table, 0)
    b, maxb = block_table.shape
    bs = pool_k.shape[1]
    k = dequantize_kv(pool_k[idx], k_scale[idx])
    v = dequantize_kv(pool_v[idx], v_scale[idx])
    return (k.reshape((b, maxb * bs) + k.shape[3:]),
            v.reshape((b, maxb * bs) + v.shape[3:]))


def reset_blocks(kv_pos: jax.Array, block_ids) -> jax.Array:
    """Mark freshly (re)allocated blocks empty.  Mandatory on allocation:
    a block recycled from another sequence still holds kv_pos values that
    could satisfy ``0 <= kv_pos <= q`` for its new owner."""
    ids = jnp.asarray(block_ids, jnp.int32)
    return kv_pos.at[ids].set(-1)


def copy_blocks(pool_k: jax.Array, pool_v: jax.Array, kv_pos: jax.Array,
                src: jax.Array, dst: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched device-side block copy (copy-on-write fork, DESIGN.md §12):
    every KV byte and kv_pos entry of block ``src[i]`` lands in block
    ``dst[i]``.  Pairs are padded with the sentinel id ``num_blocks``:
    sentinel writes drop (same out-of-range discipline as
    :func:`write_kv_paged`) and the clamped sentinel gathers feed only
    those dropped writes, so one fixed pair width serves every round."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    read = jnp.minimum(src, pool_k.shape[1] - 1)
    pool_k = pool_k.at[:, dst].set(pool_k[:, read], mode="drop")
    pool_v = pool_v.at[:, dst].set(pool_v[:, read], mode="drop")
    kv_pos = kv_pos.at[dst].set(kv_pos[read], mode="drop")
    return pool_k, pool_v, kv_pos


def copy_scales(k_scale: jax.Array, v_scale: jax.Array, src: jax.Array,
                dst: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Scale-array half of a COW block copy (:func:`copy_blocks`): the
    fp32 amax scales travel with their block's int8 values, same
    sentinel-padding drop discipline."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    read = jnp.minimum(src, k_scale.shape[1] - 1)
    k_scale = k_scale.at[:, dst].set(k_scale[:, read], mode="drop")
    v_scale = v_scale.at[:, dst].set(v_scale[:, read], mode="drop")
    return k_scale, v_scale


def write_kv(k_buf: jax.Array, v_buf: jax.Array, k_new: jax.Array,
             v_new: jax.Array, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Scatter [B,T,...] new KV into the [B,W,...] ring at pos % W."""
    w = k_buf.shape[1]
    b = k_buf.shape[0]
    t = k_new.shape[1]
    if t >= w:
        # keep only the last w tokens (prefill longer than the window)
        k_new, v_new = k_new[:, -w:], v_new[:, -w:]
        positions = positions[:, -w:]
        t = w
    slots = positions % w
    bi = jnp.arange(b)[:, None]
    k_buf = k_buf.at[bi, slots].set(k_new.astype(k_buf.dtype))
    v_buf = v_buf.at[bi, slots].set(v_new.astype(v_buf.dtype))
    return k_buf, v_buf


def write_pos(kv_pos: jax.Array, positions: jax.Array,
              valid: Optional[jax.Array] = None) -> jax.Array:
    """Update the shared slot-position map (once per model call)."""
    w = kv_pos.shape[1]
    b = kv_pos.shape[0]
    if positions.shape[1] >= w:
        positions = positions[:, -w:]
        valid = valid[:, -w:] if valid is not None else None
    slots = positions % w
    bi = jnp.arange(b)[:, None]
    newpos = positions if valid is None else jnp.where(valid, positions, -1)
    return kv_pos.at[bi, slots].set(newpos)


def commit_length(cache: CacheT, new_length: jax.Array) -> CacheT:
    out = dict(cache)
    out["length"] = new_length.astype(jnp.int32)
    return out
