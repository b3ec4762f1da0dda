"""One speculative-decoding round, fully jitted (paper §3.2 "Ragged Q").

A round with draft bucket size K (static; the engine picks the bucket from
the per-sequence SL predictions so there are at most ``sl_max - sl_min + 1``
compiled programs — the XLA-native replacement for vLLM's per-step
CUDA-graph recapture problem, DESIGN.md §3):

  1. propose      — delegated to a :class:`~repro.core.drafters.Drafter`
                    (DESIGN.md §9): a separate draft model's decode scan
                    (``model``), prompt-lookup suffix matching
                    (``ngram``), an early-exit slice of the target
                    (``self``), or any registered proposer.  The drafter
                    owns its per-sequence cache pytree and returns the
                    proposal *distribution* alongside the tokens, so
                    steps 3–4 stay proposer-agnostic.  Per-sequence
                    validity ``j < sl_i`` implements ragged SL inside the
                    fixed bucket; policies may shrink ``sl_i``
                    dynamically via the ``draft_keep`` hook.
  2. verification — ONE target forward over [pending, d_1..d_K]
                    (T = K+1) against the target cache.
  3. rejection    — exact batched ragged rejection sampling against the
                    drafter-provided q (real logits for model drafters,
                    one-hot for lookup drafters — exact either way).
  4. post-hoc     — divergence per proposed position -> policy.observe
                    (DSDE's lagging diagnostic signal; the drafter
                    defines the signal so it stays finite for point-mass
                    proposers).
  5. commit       — target cache advances by exactly 1 + n_accepted
                    tokens; the drafter commits its own cache the same
                    way (KV length arithmetic, token-history append, or
                    nothing at all).
  6. predict      — policy.predict (+ SL_cap) for the next round.

All SL-control behaviour is delegated to a :class:`SpecPolicy`
(``repro/core/policies``) and all proposal behaviour to a
:class:`Drafter` (``repro/core/drafters``), both resolved at trace time:
``spec`` and ``drafter`` are jit static arguments, so each
(policy-config, drafter-config, K) triple traces exactly one XLA program
and the dispatch costs nothing at runtime.

RNG is *identity-threaded* (DESIGN.md §7): every random draw in a round
is keyed by (request seed, the request's own round ordinal, purpose,
position) — never by host dispatch order, batch composition, or bucket
width — so temperature>0 token streams are reproducible across engine
schedules, not just greedy ones.

The engine in ``repro/serving`` strings rounds together and handles
request lifecycles / continuous batching.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig, SpecDecodeConfig
from repro.core.drafters import Drafter, build_drafter
from repro.core.policies import PolicyObservation, SpecPolicy, build_policy
from repro.core.rejection import RejectionResult, rejection_sample
from repro.models import cache as cache_lib
from repro.models.transformer import commit, forward

PyTree = Any

# RNG purpose tags: one per independent random decision a request makes.
# The engine uses PURPOSE_PREFILL for the prefill-sampled first token.
PURPOSE_DRAFT = 0
PURPOSE_ACCEPT = 1
PURPOSE_RECOVER = 2
PURPOSE_PREFILL = 3


class RoundState(NamedTuple):
    """Carried across rounds by the serving engine.

    ``draft_cache`` is whatever pytree the configured drafter threads
    round to round: a mirrored KV cache (``model``), a token-history
    buffer (``ngram``), or ``()`` (``self``).  With a paged serving
    configuration the KV pytrees are block-paged (``models/cache.py``):
    they carry the shared pools plus the per-sequence ``block_table``
    rows the allocator maintains, so block tables ride through the
    jitted round with no extra plumbing — rollback stays pure length
    arithmetic and freed speculative blocks simply return to the pool on
    the host side.

    Termination is *device-side* (DESIGN.md §7): a slot that emits its
    EOS or exhausts ``tokens_budget`` mid-round raises its own ``done``
    flag and stops consuming draft/verify work in every later round, so
    the engine can chain round N+1 onto round N before the host has
    reconciled round N's outputs (the plan → dispatch → collect
    pipeline).  The engine resets those fields when it prefills a new
    request into a slot.

    ``key`` is the CONSTANT base key; ``seed [B]`` binds each slot to
    its occupant request and ``round_idx [B]`` counts the occupant's own
    live rounds — together they derive every per-row sampling key, so
    stochastic streams are schedule-invariant (see module docstring)."""
    target_cache: PyTree
    draft_cache: PyTree
    policy_state: PyTree       # the SpecPolicy's per-sequence state pytree
    pending: jax.Array         # [B] last emitted token, not yet in caches
    sl_next: jax.Array         # [B] per-sequence SL for the next round
    key: jax.Array             # base PRNG key (constant across rounds)
    seed: jax.Array            # [B] int32 — per-slot request sampling seed
    round_idx: jax.Array       # [B] int32 — occupant's own round ordinal
    done: jax.Array            # [B] bool — slot terminated itself in-round
    tokens_budget: jax.Array   # [B] int32 — tokens the slot may still emit
    eos_id: jax.Array          # [B] int32 — per-slot EOS token (-1 = none)


class RoundOutput(NamedTuple):
    emitted: jax.Array         # [B, K+1] new tokens (pad beyond num_emitted)
    num_emitted: jax.Array     # [B] — already truncated to EOS / budget
    num_accepted: jax.Array    # [B]
    num_proposed: jax.Array    # [B]
    finished: jax.Array        # [B] bool — slot terminated THIS round
    live: jax.Array            # [B] bool — slot did real work this round
    telemetry: Dict[str, jax.Array]


def row_keys(base_key: jax.Array, seed: jax.Array, round_idx: jax.Array,
             purpose: int) -> jax.Array:
    """[B] per-row PRNG keys bound to (request seed, round ordinal,
    purpose) — the identity-threaded RNG scheme (module docstring)."""
    def one(s, r):
        kk = jax.random.fold_in(base_key, s)
        kk = jax.random.fold_in(kk, r)
        return jax.random.fold_in(kk, purpose)
    return jax.vmap(one)(seed.astype(jnp.uint32),
                         round_idx.astype(jnp.uint32))


def _match_vocab(dl: jax.Array, v: int) -> jax.Array:
    """Pad (with -inf) or slice the proposal logits to the target's
    padded-vocab width — padded entries carry no mass either way."""
    dv = dl.shape[-1]
    if dv == v:
        return dl
    if dv < v:
        return jnp.pad(dl, ((0, 0), (0, 0), (0, v - dv)),
                       constant_values=-1e30)
    return dl[..., :v]


def spec_decode_round_impl(params_t: PyTree, params_d: PyTree,
                           cfg_t: ModelConfig, drafter: Drafter,
                           spec: SpecDecodeConfig, k: int,
                           state: RoundState, active: jax.Array
                           ) -> Tuple[RoundState, RoundOutput]:
    """One full speculative round with draft bucket size ``k``.

    ``drafter`` is the frozen proposer (static — dispatch traces away);
    ``params_d`` is its parameter pytree (``None`` for parameter-free
    drafters).  ``active [B]`` masks occupied request slots (continuous
    batching); the round intersects it with ``~state.done`` so a slot
    that terminated itself device-side in an earlier — possibly not yet
    host-reconciled — round does no draft/verify work and emits
    nothing.  This is what makes back-to-back dispatch sound: the
    engine may enqueue round N+1 before it has looked at round N."""
    # both are static, so this costs nothing: a drafter built from a
    # DIFFERENT config would propose at its own temperature/knobs while
    # rejection and the policy run at ``spec``'s — silently inexact
    assert drafter.spec == spec, (
        "drafter was built from a different SpecDecodeConfig than the "
        "round is running")
    policy = build_policy(spec)     # trace-time: spec is static
    b = state.pending.shape[0]
    pad_id = cfg_t.vocab_size  # reserved padding token id (paper §3.2)

    live = active & ~state.done
    sl_i = jnp.minimum(state.sl_next, k) * live.astype(jnp.int32)

    # Each stage runs under a named scope: metadata only (the ops and the
    # kernels' names are the same without it), so a profile can charge
    # every device operation of the round to the stage that issued it.
    # --- 1. propose ---------------------------------------------------------
    with jax.named_scope("propose"):
        if k > 0:
            k_draft = row_keys(state.key, state.seed, state.round_idx,
                               PURPOSE_DRAFT)
            prop = drafter.propose(params_t, params_d, state.draft_cache,
                                   state.target_cache, state.pending, k,
                                   sl_i, policy, k_draft, live)
            sl_i = jnp.minimum(sl_i, prop.eff_sl)  # early stop / short lookup
            draft_tokens, drafted_cache = prop.tokens, prop.cache
        else:  # no-draft bucket (autoregressive policy, or an all-idle batch)
            draft_tokens = jnp.zeros((b, 0), jnp.int32)
            drafted_cache = state.draft_cache

        # replace out-of-range draft positions by the reserved pad id so
        # invalid token ids never propagate (paper §3.2); pad_id has a real
        # (padded) embedding row and is masked out of every softmax.
        pos = jnp.arange(k)[None, :]
        proposed = pos < sl_i[:, None]
        safe_drafts = jnp.where(proposed, draft_tokens, pad_id)

    # --- 2. verification ----------------------------------------------------
    with jax.named_scope("verify"):
        verify_tokens = jnp.concatenate(
            [state.pending[:, None], safe_drafts], axis=1)      # [B, K+1]
        # paged caches: verification writes positions len..len+K; only
        # j <= SL_i can ever be committed, so the rest never leaves the
        # sequence's own block budget (dense rings ignore the mask)
        verify_wm = (jnp.arange(k + 1)[None] <= sl_i[:, None]) & live[:, None]
        t_logits, t_cache_v, _ = forward(params_t, cfg_t, verify_tokens,
                                         cache=state.target_cache,
                                         mode="decode", write_mask=verify_wm)

    # --- 3. rejection sampling ----------------------------------------------
    with jax.named_scope("reject"):
        k_acc = row_keys(state.key, state.seed, state.round_idx,
                         PURPOSE_ACCEPT)
        k_rec = row_keys(state.key, state.seed, state.round_idx,
                         PURPOSE_RECOVER)
        if k > 0:
            dl = _match_vocab(prop.logits, t_logits.shape[-1])
        else:
            dl = jnp.zeros((b, 0) + t_logits.shape[-1:], t_logits.dtype)
        rej: RejectionResult = rejection_sample(
            state.key, safe_drafts, dl, t_logits, sl_i,
            temperature=spec.temperature, vocab_size=cfg_t.vocab_size,
            pad_id=pad_id, row_keys=(k_acc, k_rec))

    # --- 4. post-hoc signals --------------------------------------------------
    with jax.named_scope("signal"):
        if k > 0:
            kld = drafter.observation_kld(t_logits[:, :k], dl, safe_drafts,
                                          proposed)             # [B, K]
        else:
            kld = jnp.zeros((b, 0), jnp.float32)
        obs = PolicyObservation(
            kld=kld, proposed_valid=proposed, num_accepted=rej.num_accepted,
            num_proposed=sl_i, active=live)
        new_pstate = policy.observe(state.policy_state, obs)

    # --- 5. commit ------------------------------------------------------------
    with jax.named_scope("commit"):
        n_committed = (1 + rej.num_accepted) * live.astype(jnp.int32)
        t_cache = commit(params_t, cfg_t, verify_tokens, state.target_cache,
                         t_cache_v, n_committed)
        if k > 0:
            d_cache = drafter.commit(params_d, verify_tokens,
                                     state.draft_cache, drafted_cache,
                                     n_committed)
        else:  # the drafter was never consulted
            d_cache = state.draft_cache

        # device-side termination: truncate the emitted stream exactly the
        # way the host loop used to: walk the tokens in order, stop after
        # the first EOS or once the remaining ``tokens_budget`` is spent,
        # and raise ``done`` so later rounds skip the slot.  The host
        # merely mirrors these decisions at reconciliation — which may be
        # a full round later.
        n_raw = rej.num_emitted                                # [B]
        pos1 = jnp.arange(k + 1)[None, :]
        in_raw = pos1 < n_raw[:, None]
        is_eos = ((rej.emitted == state.eos_id[:, None])
                  & in_raw & (state.eos_id >= 0)[:, None])
        inf = jnp.int32(k + 2)                                 # > any n_raw
        eos_cut = jnp.where(is_eos.any(1),
                            jnp.argmax(is_eos, 1).astype(jnp.int32) + 1, inf)
        n_emit = jnp.minimum(n_raw, jnp.minimum(eos_cut,
                                                state.tokens_budget))
        n_emit = jnp.where(live, n_emit, 0)
        finished = live & ((n_emit == eos_cut)
                           | (n_emit == state.tokens_budget))
        new_done = state.done | finished
        new_budget = jnp.maximum(state.tokens_budget - n_emit, 0)

    # --- 6. predict next SL ----------------------------------------------------
    with jax.named_scope("predict"):
        sl_next, new_pstate, telemetry = policy.predict(new_pstate, live)

    new_state = RoundState(
        target_cache=t_cache, draft_cache=d_cache, policy_state=new_pstate,
        pending=jnp.where(live, rej.next_token, state.pending),
        sl_next=sl_next, key=state.key, seed=state.seed,
        round_idx=state.round_idx + live.astype(jnp.int32),
        done=new_done, tokens_budget=new_budget, eos_id=state.eos_id)
    out = RoundOutput(
        emitted=jnp.where(live[:, None] & (pos1 < n_emit[:, None]),
                          rej.emitted, pad_id),
        num_emitted=n_emit,
        num_accepted=rej.num_accepted * live.astype(jnp.int32),
        num_proposed=sl_i,
        finished=finished,
        live=live,
        telemetry=telemetry)
    return new_state, out


# The default single-device entry point.  The un-jitted body stays
# importable (``spec_decode_round_impl``) so the serving engine's mesh
# path can wrap it in its OWN jit with explicit ``in_shardings`` /
# ``out_shardings`` per draft bucket (DESIGN.md §5) — same trace, pinned
# layouts, no double-jit.
spec_decode_round = jax.jit(
    spec_decode_round_impl,
    static_argnames=("cfg_t", "drafter", "spec", "k"))


def init_round_state(cfg_t: ModelConfig, cfg_d: Optional[ModelConfig],
                     spec: SpecDecodeConfig, batch: int, max_len: int,
                     key: jax.Array, dtype=jnp.float32,
                     enc_len: Optional[int] = None,
                     paged: Optional[Tuple[int, int]] = None,
                     drafter: Optional[Drafter] = None,
                     kv_quant: str = "none") -> RoundState:
    """Fresh round state: target cache (dense, or block-paged when
    ``paged=(num_blocks, block_size)``) plus whatever cache pytree the
    configured drafter owns — built through the same ``paged`` geometry
    when the drafter mirrors the target pool (``model``), or its own
    structure otherwise (token history for ``ngram``, ``()`` for
    ``self``).

    ``key`` becomes the CONSTANT base key of the identity-threaded RNG;
    ``seed`` defaults to ``arange(batch)`` so direct round drivers get
    distinct per-row streams (the engine overwrites it per admission).

    The termination fields default to "never terminate" (``done`` clear,
    effectively infinite ``tokens_budget``, no EOS) so direct round
    drivers — benchmarks, the policy invariant suite — keep the
    pre-pipeline semantics; the serving engine overwrites them per slot
    at prefill."""
    policy = build_policy(spec)
    if drafter is None:
        drafter = build_drafter(spec, cfg_t, cfg_d)
    if kv_quant != "none" and paged is None:
        raise ValueError("kv_quant requires the block-paged cache "
                         "(pass paged=(num_blocks, block_size))")
    no_term = dict(
        done=jnp.zeros((batch,), bool),
        tokens_budget=jnp.full((batch,), jnp.int32(2 ** 30), jnp.int32),
        eos_id=jnp.full((batch,), -1, jnp.int32))
    if paged is not None:
        n_blocks, bs = paged
        # the serving scheduler owns the pool-vs-max_len feasibility
        # policy (prefix-cached pools may be smaller than one max-len
        # sequence); the data plane only needs drop-semantics
        t_cache = cache_lib.paged_cache_struct(cfg_t, batch, max_len,
                                               n_blocks, bs, dtype,
                                               require_full_seq=False,
                                               kv_quant=kv_quant)
    else:
        t_cache = cache_lib.cache_struct(cfg_t, batch, max_len, dtype,
                                         enc_len=enc_len)
    d_cache = drafter.init_cache(batch, max_len, dtype, paged=paged,
                                 kv_quant=kv_quant)
    return RoundState(
        target_cache=t_cache, draft_cache=d_cache,
        policy_state=policy.init_state(batch),
        pending=jnp.zeros((batch,), jnp.int32),
        sl_next=policy.initial_sl(batch),
        key=key,
        seed=jnp.arange(batch, dtype=jnp.int32),
        round_idx=jnp.zeros((batch,), jnp.int32),
        **no_term)
