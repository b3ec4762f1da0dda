"""Model assembly for all assigned architecture families.

Public API (all pure functions):

* ``model_specs(cfg)``            — Spec pytree (init / abstract / shardings)
* ``forward(params, cfg, ...)``   — one entry point, three modes:
    - ``mode="train"``    full causal pass, no cache, returns (logits, aux)
    - ``mode="prefill"``  fills the cache from a (right-padded) prompt
    - ``mode="decode"``   T tokens against the cache (T=1 plain decode,
                          T=SL_cap+1 speculative verification); KV written
                          in-pass, ``length`` untouched (engine commits)
* ``commit(params, cfg, ...)``    — commit ``n_acc`` accepted tokens:
    length arithmetic for KV families; masked state re-advance for
    recurrent families (SSM / RG-LRU), see DESIGN.md §4.

Deep homogeneous stacks (dense / moe / ssm / vlm / audio) are scanned over
a stacked-parameter leading axis — keeps the HLO small so 40 dry-run
combinations compile quickly.  The hybrid 1:2 pattern is unrolled.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.models import cache as cache_lib
from repro.models.flash import flash_attend
from repro.models.layers import (attend, attention_specs, attn_output,
                                 mlp_apply, mlp_specs,
                                 qkv_project, rmsnorm, rmsnorm_spec)
from repro.models.module import Spec
from repro.models.moe import moe_apply, moe_specs
from repro.models.rglru import rglru_block, rglru_specs
from repro.models.ssm import mamba_mixer, ssm_specs

PyTree = Any

# sequences at or above this length use blockwise (flash-style) attention
BLOCKWISE_THRESHOLD = 2048


# ---------------------------------------------------------------------------
# Spec trees
# ---------------------------------------------------------------------------

def _stack_specs(specs: PyTree, n: int) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: Spec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale),
        specs, is_leaf=lambda x: isinstance(x, Spec))


def _layer_specs(cfg: ModelConfig) -> dict:
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return {"ln1": rmsnorm_spec(cfg.d_model),
                "attn": attention_specs(cfg),
                "ln2": rmsnorm_spec(cfg.d_model),
                "mlp": mlp_specs(cfg.d_model, cfg.d_ff)}
    if fam == "moe":
        return {"ln1": rmsnorm_spec(cfg.d_model),
                "attn": attention_specs(cfg),
                "ln2": rmsnorm_spec(cfg.d_model),
                "moe": moe_specs(cfg.d_model, cfg.moe)}
    if fam == "ssm":
        return {"ln": rmsnorm_spec(cfg.d_model),
                "mixer": ssm_specs(cfg)}
    if fam == "audio":   # decoder layer
        return {"ln1": rmsnorm_spec(cfg.d_model),
                "self_attn": attention_specs(cfg),
                "ln2": rmsnorm_spec(cfg.d_model),
                "cross_attn": attention_specs(cfg),
                "ln3": rmsnorm_spec(cfg.d_model),
                "mlp": mlp_specs(cfg.d_model, cfg.d_ff)}
    raise ValueError(fam)


def _hybrid_layer_specs(cfg: ModelConfig, i: int) -> dict:
    if cache_lib.hybrid_layer_is_attention(cfg, i):
        temporal = attention_specs(cfg)
        kind = "attn"
    else:
        temporal = rglru_specs(cfg)
        kind = "rec"
    return {"kind": kind,       # static marker, stripped before init
            "ln1": rmsnorm_spec(cfg.d_model),
            "temporal": temporal,
            "ln2": rmsnorm_spec(cfg.d_model),
            "mlp": mlp_specs(cfg.d_model, cfg.d_ff)}


def model_specs(cfg: ModelConfig, vocab_pad_multiple: int = 128) -> PyTree:
    vp = cfg.padded_vocab(vocab_pad_multiple)
    specs: Dict[str, Any] = {
        "embed": Spec((vp, cfg.d_model), ("vocab", "embed"), scale=0.02),
        "final_norm": rmsnorm_spec(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = Spec((cfg.d_model, vp), ("embed", "vocab"), scale=0.02)
    if cfg.family == "hybrid":
        # homogeneous (rec, ..., rec, attn) groups scanned over a stacked
        # leading axis + an unrolled remainder; a fully-unrolled 26-layer
        # remat graph takes XLA SPMD >10 min to partition (measured)
        gsz = cfg.rglru.blocks_per_attention + 1
        ngroups, tail = divmod(cfg.num_layers, gsz)
        rec = {k: v for k, v in _hybrid_layer_specs(cfg, 0).items()
               if k != "kind"}
        attn = {k: v for k, v in _hybrid_layer_specs(cfg, gsz - 1).items()
                if k != "kind"}
        group = {"rec": _stack_specs(rec, cfg.rglru.blocks_per_attention),
                 "attn": attn}
        specs["layers"] = {
            "groups": _stack_specs(group, ngroups) if ngroups else None,
            "tail": tuple({k: v for k, v in
                           _hybrid_layer_specs(cfg, ngroups * gsz + j).items()
                           if k != "kind"} for j in range(tail)),
        }
    else:
        specs["layers"] = _stack_specs(_layer_specs(cfg), cfg.num_layers)
    if cfg.is_encoder_decoder:
        enc_cfg = cfg
        enc_layer = {"ln1": rmsnorm_spec(cfg.d_model),
                     "attn": attention_specs(enc_cfg),
                     "ln2": rmsnorm_spec(cfg.d_model),
                     "mlp": mlp_specs(cfg.d_model, cfg.d_ff)}
        specs["enc_layers"] = _stack_specs(enc_layer, cfg.num_encoder_layers)
        specs["enc_norm"] = rmsnorm_spec(cfg.d_model)
    return specs


# ---------------------------------------------------------------------------
# Attention sublayer (all modes)
# ---------------------------------------------------------------------------

def _attn_sublayer(p: dict, cfg: ModelConfig, x: jax.Array, *,
                   mode: str, positions: jax.Array,
                   rope_positions: jax.Array,
                   input_mask: Optional[jax.Array],
                   kv_buf: Optional[Tuple[jax.Array, jax.Array]],
                   kv_pos: Optional[jax.Array],
                   window: Optional[int],
                   causal: bool = True,
                   attn_sharding=None,
                   block_table: Optional[jax.Array] = None,
                   write_mask: Optional[jax.Array] = None,
                   kv_pos_pool: Optional[jax.Array] = None,
                   ) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """One attention sublayer.  ``positions`` are sequence indices (mask
    logic); ``rope_positions`` feed RoPE/M-RoPE (identical except VLM).

    With ``block_table`` set, ``kv_buf`` holds this layer's slice of the
    shared block pool ``[N, bs, KV, D]`` and ``kv_pos`` the per-sequence
    gathered position view; KV writes go through the table (``write_mask``
    drops per-token writes so a sequence stays inside its block budget).
    Decode attention then reads straight off the pool through the
    block-table-indexed Pallas kernel on TPU (``kv_pos_pool`` is the
    pool-level position map it needs), or over the gathered per-sequence
    view on the XLA reference path (exact, materializing).

    A 4-tuple ``kv_buf`` ``(k, v, k_scale, v_scale)`` is the int8
    quantized pool (DESIGN.md §13): writes quantize (values + per-slot
    amax scales), decode reads dequantize — in-register inside the
    Pallas kv-sweep, or via the gathered f32 view on the XLA path — and
    prefill attends over fake-quantized fresh K/V so every read of a
    stored vector (cold prefill, warm tail, decode/verify) sees the
    identical quantized values."""
    q, k, v = qkv_project(p, cfg, x, rope_positions)
    b, t = x.shape[:2]
    quant = kv_buf is not None and len(kv_buf) == 4

    def pin_heads(arr):
        # [B, T, H, D] head-dim TP constraint: sharding does not propagate
        # reliably into the flash scan bodies without it (measured: the
        # whole attention ran replicated on the model axis)
        if attn_sharding is not None and arr.shape[2] % attn_sharding[1] == 0:
            return jax.lax.with_sharding_constraint(arr, attn_sharding[0])
        return arr

    def expand_kv(kk, vv):
        # GQA -> MHA expansion for the XLA attention path: kv-head counts
        # (2..16) rarely divide the 16-way model axis, so grouped einsums
        # de-shard and run replicated (measured 16x attention blow-up in
        # the dry-run).  Broadcasting KV to all query heads keeps every
        # attention tensor sharded on the full head dim; the Pallas kernel
        # does native GQA grouping on TPU instead (repro/kernels).
        # When the kv count already divides the TP axis (e.g. via
        # kv_head_pad), grouped attention shards natively — skip.
        g = q.shape[2] // kk.shape[2]
        if g == 1 or (attn_sharding is not None
                      and kk.shape[2] % attn_sharding[1] == 0):
            return pin_heads(kk), pin_heads(vv)
        return (pin_heads(jnp.repeat(kk, g, axis=2)),
                pin_heads(jnp.repeat(vv, g, axis=2)))

    def pad_kv(kk, vv):
        # exact KV-head replication (kv_head_pad, §Perf): padded head j is
        # real head j // r, matching the q-head regrouping exactly
        pad = cfg.kv_head_pad
        if pad is None or kk.shape[2] >= pad:
            return kk, vv
        r = pad // kk.shape[2]
        return jnp.repeat(kk, r, axis=2), jnp.repeat(vv, r, axis=2)

    if mode == "train" or (mode == "prefill" and kv_buf is None):
        q = pin_heads(q)
        ke, ve = expand_kv(k, v)
        if t >= BLOCKWISE_THRESHOLD:
            out = flash_attend(q, ke, ve, kv_valid=input_mask,
                               window=window, causal=causal)
        else:
            kv_valid = (input_mask if input_mask is not None
                        else jnp.ones((b, t), bool))
            out = attend(q, ke, ve, q_pos=positions, kv_pos=positions,
                         kv_valid=kv_valid, window=window, causal=causal)
        return attn_output(p, out), None

    if mode == "prefill":
        # attend over fresh k/v, then store into the ring / block pool.
        # Quantized pool: attention reads the fake-quantized fresh K/V —
        # exactly the values any later dequantized read reconstructs
        # (per-head quantization commutes with pad_kv's exact head
        # replication), so cold and warm streams stay identical.
        kp_, vp_ = pad_kv(k, v)
        if quant:
            ke, ve = expand_kv(cache_lib.fake_quantize_kv(k),
                               cache_lib.fake_quantize_kv(v))
        else:
            ke, ve = expand_kv(k, v)
        if t >= BLOCKWISE_THRESHOLD:
            out = flash_attend(q, ke, ve, kv_valid=input_mask,
                               window=window, causal=causal)
        else:
            kv_valid = (input_mask if input_mask is not None
                        else jnp.ones((b, t), bool))
            out = attend(q, ke, ve, q_pos=positions, kv_pos=positions,
                         kv_valid=kv_valid, window=window, causal=causal)
        if quant:
            new_bufs = cache_lib.write_kv_paged_quant(
                kv_buf[0], kv_buf[1], kv_buf[2], kv_buf[3], kp_, vp_,
                positions, block_table)
            return attn_output(p, out), new_bufs
        if block_table is not None:
            k_buf, v_buf = cache_lib.write_kv_paged(
                kv_buf[0], kv_buf[1], kp_, vp_, positions, block_table)
        else:
            k_buf, v_buf = cache_lib.write_kv(kv_buf[0], kv_buf[1], kp_, vp_,
                                              positions)
        return attn_output(p, out), (k_buf, v_buf)

    # decode / verify: write first, then attend over the ring / pool view
    kp_, vp_ = pad_kv(k, v)
    if quant:
        k_buf, v_buf, ks_buf, vs_buf = cache_lib.write_kv_paged_quant(
            kv_buf[0], kv_buf[1], kv_buf[2], kv_buf[3], kp_, vp_,
            positions, block_table, keep=write_mask)
        new_bufs = (k_buf, v_buf, ks_buf, vs_buf)
        if kv_pos_pool is not None and kernel_ops.on_tpu():
            # TPU data plane: int8 pages stream through the table
            # lookup, their per-slot scales applied in-register in the
            # sweep
            out = kernel_ops.paged_ragged_attention_quant(
                q, k_buf, v_buf, ks_buf, vs_buf, block_table, positions,
                kv_pos_pool, window=window)
            return attn_output(p, out), new_bufs
        k_att, v_att = cache_lib.gather_paged_kv_quant(
            k_buf, v_buf, ks_buf, vs_buf, block_table)
    elif block_table is not None:
        k_buf, v_buf = cache_lib.write_kv_paged(
            kv_buf[0], kv_buf[1], kp_, vp_, positions, block_table,
            keep=write_mask)
        new_bufs = (k_buf, v_buf)
        if kv_pos_pool is not None and kernel_ops.on_tpu():
            # TPU data plane: the kernel's index maps dereference the
            # block table — no per-sequence dense view is materialized
            out = kernel_ops.paged_ragged_attention(
                q, k_buf, v_buf, block_table, positions, kv_pos_pool,
                window=window)
            return attn_output(p, out), new_bufs
        k_att, v_att = cache_lib.gather_paged_kv(k_buf, v_buf, block_table)
    else:
        k_buf, v_buf = cache_lib.write_kv(kv_buf[0], kv_buf[1], kp_, vp_,
                                          positions)
        new_bufs = (k_buf, v_buf)
        k_att, v_att = k_buf, v_buf
    kv_valid = kv_pos >= 0
    ke, ve = expand_kv(k_att, v_att)
    out = attend(q, ke, ve, q_pos=positions, kv_pos=kv_pos,
                 kv_valid=kv_valid, window=window)
    return attn_output(p, out), new_bufs


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _token_block(p: dict, cfg: ModelConfig, x: jax.Array, layer_cache: PyTree,
                 ctx: dict) -> Tuple[jax.Array, PyTree, dict]:
    """One residual block for scanned families. ``ctx`` carries mode,
    positions, masks; returns (x, new_layer_cache, aux)."""
    fam = cfg.family
    aux: dict = {}
    if fam == "ssm":
        h, new_state = mamba_mixer(
            p["mixer"], cfg, rmsnorm(x, p["ln"], cfg.norm_eps),
            state=layer_cache, update_mask=ctx.get("update_mask"),
            use_chunked=ctx["mode"] in ("train", "prefill"))
        return x + h, new_state, aux

    kv = None
    if layer_cache is not None:
        if "k_scale" in layer_cache:     # int8 pool: scales ride along
            kv = (layer_cache["k"], layer_cache["v"],
                  layer_cache["k_scale"], layer_cache["v_scale"])
        else:
            kv = (layer_cache["k"], layer_cache["v"])
    h, new_kv = _attn_sublayer(
        p["attn"], cfg, rmsnorm(x, p["ln1"], cfg.norm_eps),
        mode=ctx["mode"], positions=ctx["positions"],
        rope_positions=ctx["rope_positions"], input_mask=ctx.get("input_mask"),
        kv_buf=kv, kv_pos=ctx.get("kv_pos"), window=cfg.attention_window,
        attn_sharding=ctx.get("attn_sharding"),
        block_table=ctx.get("block_table"), write_mask=ctx.get("write_mask"),
        kv_pos_pool=ctx.get("kv_pos_pool"))
    x = x + h

    if fam == "moe":
        h, moe_aux = moe_apply(p["moe"], cfg.moe,
                               rmsnorm(x, p["ln2"], cfg.norm_eps),
                               shardings=ctx.get("moe_sharding"))
        aux.update(moe_aux)
    else:
        h = mlp_apply(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps))
    x = x + h
    new_cache = None
    if layer_cache is not None and fam != "ssm":
        new_cache = dict(layer_cache)
        if new_kv is not None:
            new_cache["k"], new_cache["v"] = new_kv[0], new_kv[1]
            if len(new_kv) == 4:
                new_cache["k_scale"], new_cache["v_scale"] = new_kv[2:]
    return x, new_cache, aux


def _cross_attend(p: dict, cfg: ModelConfig, x: jax.Array,
                  ck: jax.Array, cv: jax.Array,
                  enc_valid: jax.Array) -> jax.Array:
    """Decoder->encoder cross attention (no rope on q/k, standard for
    enc-dec translation stacks)."""
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    b, t = x.shape[:2]
    s = ck.shape[1]
    zeros = jnp.zeros((b, t), jnp.int32)
    out = attend(q, ck, cv, q_pos=zeros, kv_pos=jnp.zeros((b, s), jnp.int32),
                 kv_valid=enc_valid, window=None, causal=False)
    return attn_output(p, out)


def _audio_block(p: dict, cfg: ModelConfig, x: jax.Array, layer_cache: PyTree,
                 ctx: dict) -> Tuple[jax.Array, PyTree, dict]:
    kv = ((layer_cache["k"], layer_cache["v"])
          if layer_cache is not None and "k" in layer_cache else None)
    h, new_kv = _attn_sublayer(
        p["self_attn"], cfg, rmsnorm(x, p["ln1"], cfg.norm_eps),
        mode=ctx["mode"], positions=ctx["positions"],
        rope_positions=ctx["rope_positions"], input_mask=ctx.get("input_mask"),
        kv_buf=kv, kv_pos=ctx.get("kv_pos"), window=None,
        attn_sharding=ctx.get("attn_sharding"))
    x = x + h
    ck = layer_cache["cross_k"] if layer_cache is not None else ctx["cross_k"]
    cv = layer_cache["cross_v"] if layer_cache is not None else ctx["cross_v"]
    enc_valid = ctx["enc_valid"]
    x = x + _cross_attend(p["cross_attn"], cfg,
                          rmsnorm(x, p["ln2"], cfg.norm_eps), ck, cv, enc_valid)
    x = x + mlp_apply(p["mlp"], rmsnorm(x, p["ln3"], cfg.norm_eps))
    new_cache = None
    if layer_cache is not None:
        new_cache = dict(layer_cache)
        if new_kv is not None:
            new_cache["k"], new_cache["v"] = new_kv
    return x, new_cache, dict()


def _audio_train_stack(params: PyTree, cfg: ModelConfig,
                       encoder_embeds: jax.Array,
                       enc_valid: Optional[jax.Array]) -> PyTree:
    """Encoder pass + per-decoder-layer cross KV, for cache-less (train)
    audio forwards."""
    enc_out = encode(params, cfg, encoder_embeds, enc_valid)
    ck, cv = build_cross_cache(params, cfg, enc_out)
    return {"cross_k": ck, "cross_v": cv}


def _hybrid_block(p: dict, cfg: ModelConfig, i: int, x: jax.Array,
                  layer_cache: PyTree, ctx: dict
                  ) -> Tuple[jax.Array, PyTree, dict]:
    is_attn = cache_lib.hybrid_layer_is_attention(cfg, i)
    if is_attn:
        kv = ((layer_cache["k"], layer_cache["v"])
              if layer_cache is not None else None)
        h, new_kv = _attn_sublayer(
            p["temporal"], cfg, rmsnorm(x, p["ln1"], cfg.norm_eps),
            mode=ctx["mode"], positions=ctx["positions"],
            rope_positions=ctx["rope_positions"],
            input_mask=ctx.get("input_mask"), kv_buf=kv,
            kv_pos=ctx.get("kv_pos"),
            window=cfg.rglru.local_attention_window,
            attn_sharding=ctx.get("attn_sharding"))
        new_cache = None
        if layer_cache is not None:
            new_cache = dict(layer_cache)
            if new_kv is not None:
                new_cache["k"], new_cache["v"] = new_kv
    else:
        h, new_state = rglru_block(
            p["temporal"], cfg, rmsnorm(x, p["ln1"], cfg.norm_eps),
            state=layer_cache, update_mask=ctx.get("update_mask"),
            sequential=ctx["mode"] == "decode")
        new_cache = new_state if layer_cache is not None else None
    x = x + h
    x = x + mlp_apply(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps))
    return x, new_cache, dict()


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------

def _zero_aux(cfg: ModelConfig) -> dict:
    if cfg.family == "moe":
        return {"load_balance_loss": jnp.zeros((), jnp.float32),
                "router_z_loss": jnp.zeros((), jnp.float32),
                "expert_fraction": jnp.zeros((cfg.moe.num_experts,), jnp.float32),
                "dropped_fraction": jnp.zeros((), jnp.float32)}
    return {}


# ``optimization_barrier`` has no differentiation rule; wrap it in an
# identity custom_vjp so the barrier still pins the remat stash layout on
# the forward pass while gradients flow straight through on the backward.
@jax.custom_vjp
def _stash_barrier(x: jax.Array) -> jax.Array:
    return jax.lax.optimization_barrier(x)


def _stash_barrier_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _stash_barrier_bwd(_, g):
    return (g,)


_stash_barrier.defvjp(_stash_barrier_fwd, _stash_barrier_bwd)


def _scan_stack(params: PyTree, cfg: ModelConfig, x: jax.Array,
                stacked_cache: Optional[PyTree], ctx: dict, remat: bool
                ) -> Tuple[jax.Array, Optional[PyTree], dict]:
    block = _audio_block if cfg.family == "audio" else _token_block
    aux0 = _zero_aux(cfg)

    def body(carry, layer_in):
        xc, aux_acc = carry
        p_l, c_l = layer_in
        # barrier keeps the remat stash in the carry's own dtype (bf16):
        # without it XLA saves the f32 rmsnorm-converted copy of every
        # layer input (2x stash memory, measured in the dry-run)
        xc = _stash_barrier(xc)
        xc, c_new, aux = block(p_l, cfg, xc, c_l, ctx)
        if ctx.get("act_sharding") is not None:
            # sequence-parallel residual stream between blocks: bounds the
            # remat-stashed activations per chip (DESIGN.md §5)
            xc = jax.lax.with_sharding_constraint(xc, ctx["act_sharding"])
        aux_acc = jax.tree_util.tree_map(jnp.add, aux_acc, aux) if aux else aux_acc
        return (xc, aux_acc), c_new

    if remat:
        body = jax.checkpoint(body)
    (x, aux), new_cache = jax.lax.scan(body, (x, aux0),
                                       (params, stacked_cache))
    if cfg.family == "moe":
        aux = jax.tree_util.tree_map(lambda a: a / cfg.num_layers, aux)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Cache <-> per-layer views
# ---------------------------------------------------------------------------

def _stacked_cache_view(cfg: ModelConfig, cache: Optional[cache_lib.CacheT]
                        ) -> Optional[PyTree]:
    if cache is None:
        return None
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        out = {"k": cache["k"], "v": cache["v"]}
        if "k_scale" in cache:           # int8 pool: per-layer scales
            out["k_scale"] = cache["k_scale"]
            out["v_scale"] = cache["v_scale"]
        return out
    if fam == "ssm":
        return {"ssd": cache["ssd"], "conv": cache["conv"]}
    if fam == "audio":
        return {"k": cache["k"], "v": cache["v"],
                "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}
    raise ValueError(fam)


def _store_stacked(cfg: ModelConfig, cache: cache_lib.CacheT,
                   new_stack: PyTree) -> cache_lib.CacheT:
    out = dict(cache)
    for k, v in new_stack.items():
        if v is not None:
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _embed(params: PyTree, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens]


def _lm_head(params: PyTree, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("btd,vd->btv", x, params["embed"])
    return jnp.einsum("btd,dv->btv", x, params["lm_head"])


def encode(params: PyTree, cfg: ModelConfig, embeds: jax.Array,
           enc_valid: Optional[jax.Array] = None) -> jax.Array:
    """Bidirectional encoder over frontend embeddings (audio)."""
    b, s, _ = embeds.shape
    if enc_valid is None:
        enc_valid = jnp.ones((b, s), bool)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    ctx = {"mode": "train", "positions": positions,
           "rope_positions": positions, "input_mask": enc_valid}

    def body(x, p_l):
        h, _ = _attn_sublayer(
            p_l["attn"], cfg, rmsnorm(x, p_l["ln1"], cfg.norm_eps),
            mode="train", positions=ctx["positions"],
            rope_positions=ctx["rope_positions"], input_mask=enc_valid,
            kv_buf=None, kv_pos=None, window=None, causal=False)
        x = x + h
        x = x + mlp_apply(p_l["mlp"], rmsnorm(x, p_l["ln2"], cfg.norm_eps))
        return x, None

    x, _ = jax.lax.scan(body, embeds, params["enc_layers"])
    return rmsnorm(x, params["enc_norm"], cfg.norm_eps)


def build_cross_cache(params: PyTree, cfg: ModelConfig, enc_out: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """Per-decoder-layer cross K/V from encoder output: [L,B,S,KV,D]."""
    def one(p_l):
        k = jnp.einsum("bsd,dhk->bshk", enc_out, p_l["cross_attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, p_l["cross_attn"]["wv"])
        if cfg.qkv_bias:
            k = k + p_l["cross_attn"]["bk"]
            v = v + p_l["cross_attn"]["bv"]
        return k, v

    _, (ks, vs) = jax.lax.scan(lambda _, p: (None, one(p)), None,
                               params["layers"])
    return ks, vs


def _hybrid_forward(params: PyTree, cfg: ModelConfig, x: jax.Array,
                    cache, ctx: dict, remat: bool):
    """RecurrentGemma stack: scan over (rec x bpa, attn) groups + an
    unrolled remainder of rec layers.  Cache layout: rec caches in layer
    order (grouped prefix [ngroups*bpa], then tail), attn caches [ngroups].
    """
    gsz = cfg.rglru.blocks_per_attention + 1
    bpa = cfg.rglru.blocks_per_attention
    ngroups, tail = divmod(cfg.num_layers, gsz)
    lp = params["layers"]

    def rec_block(p_l, xx, c_l):
        h, new_state = rglru_block(
            p_l["temporal"], cfg, rmsnorm(xx, p_l["ln1"], cfg.norm_eps),
            state=c_l, update_mask=ctx.get("update_mask"),
            sequential=ctx["mode"] == "decode")
        xx = xx + h
        xx = xx + mlp_apply(p_l["mlp"], rmsnorm(xx, p_l["ln2"], cfg.norm_eps))
        return xx, (new_state if c_l is not None else None)

    def attn_block(p_l, xx, c_l):
        kv = (c_l["k"], c_l["v"]) if c_l is not None else None
        h, new_kv = _attn_sublayer(
            p_l["temporal"], cfg, rmsnorm(xx, p_l["ln1"], cfg.norm_eps),
            mode=ctx["mode"], positions=ctx["positions"],
            rope_positions=ctx["rope_positions"],
            input_mask=ctx.get("input_mask"), kv_buf=kv,
            kv_pos=ctx.get("kv_pos"),
            window=cfg.rglru.local_attention_window,
            attn_sharding=ctx.get("attn_sharding"),
            block_table=ctx.get("block_table"),
            write_mask=ctx.get("write_mask"),
            kv_pos_pool=ctx.get("kv_pos_pool"))
        xx = xx + h
        xx = xx + mlp_apply(p_l["mlp"], rmsnorm(xx, p_l["ln2"], cfg.norm_eps))
        c_new = None
        if c_l is not None:
            c_new = dict(c_l)
            if new_kv is not None:
                c_new["k"], c_new["v"] = new_kv
        return xx, c_new

    new_cache = dict(cache) if cache is not None else None
    if ngroups:
        if cache is not None:
            rg = cfg.rglru
            lru_g = cache["lru"][:ngroups * bpa].reshape(
                (ngroups, bpa) + cache["lru"].shape[1:])
            conv_g = cache["conv"][:ngroups * bpa].reshape(
                (ngroups, bpa) + cache["conv"].shape[1:])
            gcache = {"lru": lru_g, "conv": conv_g,
                      "k": cache["k"], "v": cache["v"]}
        else:
            gcache = None

        def group_body(xx, gin):
            p_g, c_g = gin
            new_rec_lru, new_rec_conv = [], []
            for j in range(bpa):
                p_r = jax.tree_util.tree_map(lambda a: a[j], p_g["rec"])
                c_r = (None if c_g is None else
                       {"lru": c_g["lru"][j], "conv": c_g["conv"][j]})
                xx, c_rn = rec_block(p_r, xx, c_r)
                if c_rn is not None:
                    new_rec_lru.append(c_rn["lru"])
                    new_rec_conv.append(c_rn["conv"])
            c_a = (None if c_g is None else
                   {"k": c_g["k"], "v": c_g["v"]})
            xx, c_an = attn_block(p_g["attn"], xx, c_a)
            if ctx.get("act_sharding") is not None:
                xx = jax.lax.with_sharding_constraint(xx, ctx["act_sharding"])
            c_out = None
            if c_g is not None:
                c_out = {"lru": jnp.stack(new_rec_lru),
                         "conv": jnp.stack(new_rec_conv),
                         "k": c_an["k"], "v": c_an["v"]}
            return xx, c_out

        body = jax.checkpoint(group_body) if remat else group_body
        x, gnew = jax.lax.scan(body, x, (lp["groups"], gcache))
        if cache is not None:
            new_cache["k"], new_cache["v"] = gnew["k"], gnew["v"]
            lru_flat = gnew["lru"].reshape((-1,) + gnew["lru"].shape[2:])
            conv_flat = gnew["conv"].reshape((-1,) + gnew["conv"].shape[2:])
        else:
            lru_flat = conv_flat = None

    # unrolled remainder (rec layers)
    tail_lru, tail_conv = [], []
    for j in range(tail):
        p_l = lp["tail"][j]
        idx = ngroups * bpa + j
        c_l = (None if cache is None else
               {"lru": cache["lru"][idx], "conv": cache["conv"][idx]})
        x, c_n = rec_block(p_l, x, c_l)
        if c_n is not None:
            tail_lru.append(c_n["lru"])
            tail_conv.append(c_n["conv"])
    if cache is not None:
        parts_l = ([lru_flat] if ngroups else []) +             ([jnp.stack(tail_lru)] if tail_lru else [])
        parts_c = ([conv_flat] if ngroups else []) +             ([jnp.stack(tail_conv)] if tail_conv else [])
        if parts_l:
            new_cache["lru"] = jnp.concatenate(parts_l, 0)
            new_cache["conv"] = jnp.concatenate(parts_c, 0)
    return x, new_cache


def forward(params: PyTree, cfg: ModelConfig, tokens: Optional[jax.Array],
            *, cache: Optional[cache_lib.CacheT] = None, mode: str = "train",
            embeds: Optional[jax.Array] = None,
            input_mask: Optional[jax.Array] = None,
            rope_positions: Optional[jax.Array] = None,
            update_mask: Optional[jax.Array] = None,
            encoder_embeds: Optional[jax.Array] = None,
            enc_valid: Optional[jax.Array] = None,
            write_mask: Optional[jax.Array] = None,
            act_sharding=None, attn_sharding=None, moe_sharding=None,
            remat: bool = False
            ) -> Tuple[jax.Array, Optional[cache_lib.CacheT], dict]:
    """Unified forward. Returns (logits [B,T,Vp], new_cache, aux).

    ``write_mask [B, T]`` (decode mode, paged caches only): positions
    whose mask is False skip the KV write entirely — the speculative
    round masks per-sequence draft tails so a short-SL sequence never
    writes outside its allocated blocks.  Dense ring caches ignore it
    (writes behind ``length`` are overwritten-or-masked anyway)."""
    assert mode in ("train", "prefill", "decode")
    x = embeds if embeds is not None else _embed(params, cfg, tokens)
    b, t = x.shape[:2]

    if mode in ("train", "prefill") or cache is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    else:
        positions = cache["length"][:, None] + jnp.arange(t)[None]
    if rope_positions is None:
        rope_positions = positions

    if (mode == "prefill" and input_mask is not None
            and update_mask is None and has_recurrent_state(cfg)):
        # right-padded ragged prompts: recurrent state must not advance
        # over pad positions (attention handles this via kv validity)
        update_mask = input_mask.astype(jnp.float32)
    ctx = {"mode": mode, "positions": positions,
           "rope_positions": rope_positions, "input_mask": input_mask,
           "update_mask": update_mask, "act_sharding": act_sharding,
           "attn_sharding": attn_sharding, "moe_sharding": moe_sharding}
    new_cache = None

    kv_pos_store = None
    if cache is not None and "kv_pos" in cache:
        valid = input_mask if mode == "prefill" else None
        if cache_lib.is_paged(cache):
            keep = write_mask if mode == "decode" else None
            kv_pos_store = cache_lib.write_pos_paged(
                cache["kv_pos"], positions, cache["block_table"], valid, keep)
            ctx["kv_pos"] = cache_lib.gather_paged_pos(kv_pos_store,
                                                       cache["block_table"])
            ctx["block_table"] = cache["block_table"]
            if mode == "decode":
                ctx["write_mask"] = write_mask
                ctx["kv_pos_pool"] = kv_pos_store
        else:
            kv_pos_store = cache_lib.write_pos(cache["kv_pos"], positions,
                                               valid)
            ctx["kv_pos"] = kv_pos_store
    if cfg.family == "audio":
        if cache is not None:
            ctx["enc_valid"] = cache["enc_valid"]
        else:
            assert encoder_embeds is not None, \
                "audio train mode needs encoder_embeds"
            ctx["enc_valid"] = (enc_valid if enc_valid is not None else
                                jnp.ones(encoder_embeds.shape[:2], bool))

    if cfg.family == "hybrid":
        aux = {}
        x, new_cache = _hybrid_forward(params, cfg, x, cache, ctx,
                                       remat and mode == "train")
        if new_cache is not None and kv_pos_store is not None:
            new_cache["kv_pos"] = kv_pos_store
    else:
        stacked = _stacked_cache_view(cfg, cache)
        if cfg.family == "audio" and cache is None:
            stacked = _audio_train_stack(params, cfg, encoder_embeds,
                                         ctx["enc_valid"])
        x, new_stack, aux = _scan_stack(params["layers"], cfg, x, stacked,
                                        ctx, remat and mode == "train")
        if cache is not None:
            new_cache = _store_stacked(cfg, cache, new_stack)
            if kv_pos_store is not None and "kv_pos" in cache:
                new_cache["kv_pos"] = kv_pos_store

    logits = _lm_head(params, cfg, x)
    return logits, new_cache, aux


def commit(params: PyTree, cfg: ModelConfig, tokens: jax.Array,
           snapshot: cache_lib.CacheT, verified: cache_lib.CacheT,
           n_committed: jax.Array) -> cache_lib.CacheT:
    """Commit ``n_committed[b]`` of the T tokens just verified.

    KV families: stale ring slots are masked by ``length`` — O(1).
    Recurrent families: masked re-advance from the snapshot (identity on
    masked steps) so state reflects exactly the accepted prefix.
    """
    new_len = snapshot["length"] + n_committed.astype(jnp.int32)
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        return cache_lib.commit_length(verified, new_len)
    t = tokens.shape[1]
    update_mask = (jnp.arange(t)[None] < n_committed[:, None]).astype(jnp.float32)
    _, advanced, _ = forward(params, cfg, tokens, cache=snapshot,
                             mode="decode", update_mask=update_mask)
    return cache_lib.commit_length(advanced, new_len)


def has_recurrent_state(cfg: ModelConfig) -> bool:
    return cfg.family in ("ssm", "hybrid")
