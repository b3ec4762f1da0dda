"""DSDE serving engine: a plan → dispatch → collect pipeline over the
jitted speculative round (DESIGN.md §7).

The engine composes:
  * :class:`LookaheadScheduler`  — queue/slot admission from SL predictions
    plus, under the paged KV layout, the block allocator (grow on demand,
    preempt when the pool runs dry);
  * ``spec_decode_round``        — the jitted speculative round (bucketed by
    K so there is one XLA program per draft length, never per step) with
    *device-side termination*: a slot that emits EOS or exhausts its token
    budget deactivates itself in-round, so rounds can be chained
    back-to-back without waiting for host EOS checks;
  * batched prefill              — requests admitted together that share a
    prompt bucket prefill as ONE multi-row program (dense rows or a
    multi-row paged-table view), not two jit calls per request.

Two execution modes share every phase:

  * synchronous (default)       — ``step()`` = plan, dispatch, collect;
    the host reconciles each round before dispatching the next (the
    lockstep loop, simplest to reason about, what the unit tests drive).
  * pipelined (``ServingConfig.pipelined``) — ``run()`` enqueues round
    N+1 immediately after round N and reconciles the host ONE ROUND
    BEHIND: token distribution, EOS bookkeeping, block shrink and the
    round log all happen while the device is already crunching the next
    round.  Greedy token streams are byte-identical to the synchronous
    engine (speculative decoding is exact, and truncation semantics live
    on the device); scheduling-side telemetry (round counts, bucket
    sequence) may differ by the one-round lag.

Every phase runs under a profiler span (``jax.profiler.TraceAnnotation``,
which costs a flag check while no profile is being taken): each
``pump()`` is a step ``engine.round`` numbered by the round it
dispatches, holding ``engine.plan`` (``engine.admit`` ⊃
``engine.prefill`` per prefill group ⊃ ``engine.prefill_draft``, the
drafter's side of it; ``engine.pick_bucket``,
``engine.plan_blocks`` ⊃ ``engine.block_sync``), ``engine.dispatch``
(``engine.round_call``) and ``engine.collect`` (``engine.collect_wait``,
``engine.reconcile``, ``engine.block_sync``).  The round log books each
phase's thread CPU seconds (``plan_cpu_s``, ``dispatch_cpu_s``,
``collect_cpu_s``) to the round it served, and names the round by the
same ordinal (``round``).

This runs for real on CPU (reduced models) and is the same code path the
TPU launch scripts drive; only meshes/shardings differ (repro/launch).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import prefill as prefill_lib
from repro.core import spec_decode as sd
from repro.core.config import (ModelConfig, ServingConfig, SpecDecodeConfig)
from repro.core.drafters import build_drafter
from repro.core.policies import build_policy
from repro.core.sampling import sample_token
from repro.kernels import ops as kernel_ops
from repro.kernels.ragged_attention import live_blocks
from repro.models import cache as cache_lib
from repro.models.transformer import has_recurrent_state, model_specs
from repro.serving.latency_model import RoundLatencyModel
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import LookaheadScheduler

PyTree = Any

_span = jax.profiler.TraceAnnotation

# Mesh-path round programs, shared ACROSS engine instances: keyed by the
# exact trace identity (model/drafter/spec/bucket) plus the serving-mesh
# plan and the declared state sharding tree (NamedShardings are hashable),
# so e.g. the sync and pipelined engines of one benchmark reuse the same
# compiled rounds instead of re-tracing per engine.
_MESH_ROUND_JITS: Dict[Any, Any] = {}


def _bucket(n: int, minimum: int = 16, cap: Optional[int] = None) -> int:
    """Power-of-two prompt bucket, clamped so a long prompt can never
    round up past the KV budget (a bucket wider than ``cap`` would build
    a prefill program whose writes get truncated)."""
    b = max(minimum, 1 << math.ceil(math.log2(max(n, 1))))
    if cap is not None:
        b = min(b, cap)
        assert n <= b, f"prompt of {n} tokens exceeds the KV budget {cap}"
    return b


class _DispatchRecord:
    """Host-side snapshot of one dispatched round, reconciled by
    ``collect`` — possibly a full round later, after ``plan`` has already
    mutated the engine's device state.  Everything ``collect`` needs is
    captured here by reference at dispatch time: the (request, slot)
    occupancy as the round saw it, the prefill-sampled first tokens
    riding this round, and the round's output arrays (immutable jax
    arrays whose host copies were started with ``copy_to_host_async``).
    """

    __slots__ = ("k", "rows", "admits", "out", "sl_next", "t_dispatch",
                 "prefill_tokens", "ordinal", "plan_cpu_s", "dispatch_cpu_s")

    def __init__(self, k: int, rows, admits, out, sl_next, t_dispatch,
                 prefill_tokens=0, ordinal=0, plan_cpu_s=0.0):
        self.k = k
        self.ordinal = ordinal    # the engine's count of earlier dispatches
        self.rows = rows          # [(req, slot, preemptions-at-dispatch)]
        self.admits = admits      # [(fresh_reqs, pend [R] jax, fresh_idx,
                                  #   preemptions-at-prefill)]
        self.out = out            # RoundOutput (device futures)
        self.sl_next = sl_next    # [B] jax — post-round SL predictions
        self.t_dispatch = t_dispatch
        # prefill tokens computed by the admission wave riding this
        # round's wall interval (the latency model's c_prefill regressor)
        self.prefill_tokens = prefill_tokens
        # thread CPU seconds of the plan and dispatch that served it
        self.plan_cpu_s = plan_cpu_s
        self.dispatch_cpu_s = 0.0


class _RoundHost(NamedTuple):
    """A collected round's outputs, copied to the host."""
    emitted: np.ndarray
    n_emit: np.ndarray
    n_acc: np.ndarray
    n_prop: np.ndarray
    fin: np.ndarray
    live: np.ndarray
    sl_next: np.ndarray
    admit_pends: List[np.ndarray]


class ServingEngine:
    def __init__(self, params_target: PyTree, cfg_target: ModelConfig,
                 params_draft: Optional[PyTree],
                 cfg_draft: Optional[ModelConfig],
                 spec: SpecDecodeConfig, serving: ServingConfig,
                 seed: int = 0, mesh: Optional[Any] = None,
                 latency_model: Optional[RoundLatencyModel] = None):
        """``mesh``: an optional ``jax.sharding.Mesh`` with ``data`` /
        ``model`` axes.  None (the default) is the single-device engine,
        bit-for-bit unchanged.  With a mesh, params and round state are
        placed under the §5 ``serve`` rule set and every round runs
        through a jit with explicit in/out shardings — greedy token
        streams stay byte-identical to the single-device engine
        (tests/test_serving_mesh.py).

        ``latency_model``: a pre-seeded :class:`RoundLatencyModel`
        (e.g. warm-started from a calibration sweep's round log); None
        builds a fresh one.  Either way the engine feeds it one sample
        per collected round and installs it on the scheduler, where the
        SLO policy hooks and admission gate consult it (DESIGN.md §15)."""
        self.pt, self.cfg_t = params_target, cfg_target
        self.pd, self.cfg_d = params_draft, cfg_draft
        # the drafter (DESIGN.md §9) — the proposer half of every round.
        # A goodput cost left unresolved (None) is sourced from the
        # drafter's own step_cost() BEFORE any policy is built, so the
        # resolved spec is the single static key everywhere downstream.
        drafter = build_drafter(spec, cfg_target, cfg_draft)
        if drafter.uses_draft_model() and (params_draft is None
                                           or cfg_draft is None):
            raise ValueError(
                f"drafter {spec.drafter!r} needs draft-model params/config"
                " (params_draft / cfg_draft must not be None)")
        if spec.goodput_draft_cost is None:
            spec = dataclasses.replace(spec,
                                       goodput_draft_cost=drafter.step_cost())
            drafter = build_drafter(spec, cfg_target, cfg_draft)
        self.drafter = drafter
        self.spec = spec
        self.policy = build_policy(spec)
        self.serving = serving
        self.paged = serving.paged_kv
        if self.paged and not (cache_lib.supports_paged(cfg_target)
                               and (not drafter.mirrors_kv()
                                    or cache_lib.supports_paged(cfg_draft))):
            raise ValueError(
                "paged_kv=True but family pair "
                f"({cfg_target.family}, "
                f"{cfg_draft.family if cfg_draft else None}) has no paged "
                "KV layout (supported: dense/moe/vlm/hybrid)")
        # quantized KV storage (DESIGN.md §13): int8 pools exist only on
        # the paged data plane, and only for families whose paged cache
        # is a pure attention pool — hybrid recurrent leaves stay fp.
        self.kv_quant = serving.kv_quant
        if self.kv_quant != "none":
            if not self.paged:
                raise ValueError("kv_quant requires paged_kv=True")
            if not (cache_lib.supports_kv_quant(cfg_target)
                    and (not drafter.mirrors_kv()
                         or cache_lib.supports_kv_quant(cfg_draft))):
                raise ValueError(
                    f"kv_quant={self.kv_quant!r} but family pair "
                    f"({cfg_target.family}, "
                    f"{cfg_draft.family if cfg_draft else None}) has no "
                    "quantized paged layout (supported: dense/moe/vlm)")
        # prefix caching (DESIGN.md §12): effective only on the paged
        # data plane with attention-only families — recurrent per-slot
        # state (hybrid lru/conv, ssm) cannot be recovered from shared
        # pool blocks, so a cache-hit admission could not reconstruct
        # it.  When the drafter mirrors the pool its family must be
        # attention-only too.
        self.prefix_caching = bool(
            serving.prefix_caching and self.paged
            and not has_recurrent_state(cfg_target)
            and (not drafter.mirrors_kv()
                 or not has_recurrent_state(cfg_draft)))
        # model-free drafters have no mirrored draft pool: the mirror's
        # block budget returns to the target pool, so the same
        # ServingConfig admits proportionally more in-flight sequences
        # (the per-sequence charge halves, DESIGN.md §9)
        block_bytes = (cache_lib.kv_block_bytes(cfg_target,
                                                serving.kv_block_size,
                                                self.kv_quant)
                       if self.paged else 0)
        self.scheduler = LookaheadScheduler(serving, spec,
                                            policy=self.policy,
                                            kv_mirror=drafter.mirrors_kv(),
                                            prefix_cache=self.prefix_caching,
                                            block_bytes=block_bytes)
        # the analytic per-round latency model (DESIGN.md §15): fed one
        # (features, wall_s) sample per collect, installed on the
        # scheduler so the SLO admission gate and the policy host hooks
        # (via HostRoundContext) consult the same fit
        self.latency_model = (latency_model if latency_model is not None
                              else RoundLatencyModel())
        self.scheduler.latency_model = self.latency_model
        self.key = jax.random.PRNGKey(seed)
        b = serving.max_batch_size
        paged_arg = ((self.scheduler.kv_blocks_total(),
                      serving.kv_block_size) if self.paged else None)
        make_state = functools.partial(
            sd.init_round_state, cfg_target, cfg_draft, spec, b,
            serving.max_seq_len, self.key, paged=paged_arg, drafter=drafter,
            kv_quant=self.kv_quant)
        # --- serving mesh (DESIGN.md §5): place params + state, build the
        # per-bucket round jits with explicit in/out shardings ------------
        self.mesh = mesh
        self._plan = None
        self._mesh_round_fns: Dict[int, Any] = {}
        if mesh is None:
            self.state = make_state()
        else:
            from repro.launch import sharding as shd
            rules = shd.serve_rules(mesh, b)
            self._plan = shd.ServeMeshPlan(mesh=mesh, rules=rules)
            # params built already sharded (launch/serve.py) stay where
            # they are; device_put only moves host- or single-device trees
            self._pt_sh = shd.param_shardings(model_specs(cfg_target),
                                              mesh, rules)
            self.pt = jax.device_put(self.pt, self._pt_sh)
            if self.pd is not None:
                self._pd_sh = shd.param_shardings(model_specs(cfg_draft),
                                                  mesh, rules)
                self.pd = jax.device_put(self.pd, self._pd_sh)
            else:       # model-free drafter: no draft params to place
                self._pd_sh = shd.replicated(mesh)
            # the round state (KV pools included) is born sharded: no
            # device ever holds the whole pool
            self._state_sh = shd.round_state_shardings(
                jax.eval_shape(make_state), mesh, rules)
            self.state = jax.jit(  # speclint: disable=JX004 (runs once)
                make_state, out_shardings=self._state_sh)()
        # host-side mirror of state.sl_next, refreshed once per collect
        # while the round's other outputs are already being transferred —
        # the bucket choice never triggers its own device->host sync.
        # Under the pipelined loop this mirror is ONE ROUND STALE at
        # dispatch time; block planning adds worst-case slack for that.
        self._sl_next_host = np.full((b,), self.policy.initial_sl_value(),
                                     np.int32)
        # pipeline bookkeeping
        self._inflight: Optional[_DispatchRecord] = None
        # (fresh requests, pend tokens [R], their row indices, their
        # preemption counts at prefill) awaiting the next dispatch
        self._pending_admits: List[Tuple[List[Request], jax.Array,
                                         List[int], List[int]]] = []
        self._planned_k: Optional[int] = None
        self._finished_at_prefill: List[Request] = []
        # prefill tokens computed since the last dispatch — snapshotted
        # into each dispatch record as the latency model's c_prefill
        # regressor for the round interval they ride
        self._prefill_tokens_pending = 0
        # thread CPU seconds of plans since the last dispatch, booked to
        # the round that dispatch starts
        self._plan_cpu_pending = 0.0
        # telemetry
        self.rounds = 0
        self.draft_steps = 0            # padded bucket steps (k+1)
        self.draft_steps_effective = 0  # max per-seq proposals + 1 (what a
                                        # dynamic-shape runtime would run)
        self.emitted_total = 0
        self.round_log: List[Dict[str, float]] = []
        # prefix-cache watermarks: the scheduler keeps lifetime totals,
        # the round log wants per-round deltas
        self._hit_blocks_logged = 0
        self._cow_logged = 0
        self._prefix_tok_logged = 0
        self._prefix_hit_tok_logged = 0

    # ------------------------------------------------------------------ rng
    def _request_keys(self, reqs: List[Request]) -> jax.Array:
        """[R] per-request prefill-sampling keys: bound to the request's
        identity alone (identity-threaded RNG, DESIGN.md §7), so the
        first token a request samples is independent of admission
        grouping, schedule, and batch composition."""
        ids = jnp.asarray([r.request_id for r in reqs], jnp.int32)
        zero = jnp.zeros_like(ids)
        return sd.row_keys(self.key, ids, zero, sd.PURPOSE_PREFILL)

    # ------------------------------------------------------------- lifecycle
    def submit(self, req: Request) -> None:
        self.scheduler.submit(req)

    # ------------------------------------------------------------ the round
    def _round_fn(self, k: int):
        """The jitted round for draft bucket ``k`` as a ``(state, active)
        -> (state, out)`` callable.  Off-mesh: the module-level
        ``sd.spec_decode_round``, unchanged.  On a mesh: a per-bucket jit
        over the same traced body with explicit ``in_shardings`` /
        ``out_shardings`` — inputs are resharded back to the §5 layouts
        if the host's eager per-slot updates drifted them, outputs are
        pinned to those layouts, so consecutive rounds at a fixed bucket
        reuse ONE program whatever the host did in between (the
        no-recompile guard in tests/test_serving_mesh.py) and GSPMD
        never round-trips the caches through replicated layouts."""
        if self.mesh is None:
            return lambda state, active: sd.spec_decode_round(
                self.pt, self.pd, self.cfg_t, self.drafter, self.spec, k,
                state, active)
        fn = self._mesh_round_fns.get(k)
        if fn is None:
            key = (self.cfg_t, self.drafter, self.spec, k, self._plan,
                   jax.tree_util.tree_structure(self._state_sh),
                   tuple(jax.tree_util.tree_leaves(self._state_sh)))
            fn = _MESH_ROUND_JITS.get(key)
            if fn is None:
                cfg_t, drafter, spec = self.cfg_t, self.drafter, self.spec
                plan = self._plan

                def body(pt, pd, state, active):
                    # the Pallas calls run per shard, on the plan's specs
                    with kernel_ops.sharded_kernels(plan):
                        return sd.spec_decode_round_impl(
                            pt, pd, cfg_t, drafter, spec, k, state, active)
                rep = self._plan.replicated()
                fn = jax.jit(body,
                             in_shardings=(self._pt_sh, self._pd_sh,
                                           self._state_sh, rep),
                             out_shardings=(self._state_sh, rep))
                _MESH_ROUND_JITS[key] = fn
            self._mesh_round_fns[k] = fn
        return lambda state, active: fn(self.pt, self.pd, state, active)

    # ----------------------------------------------------------- block plane
    def _table_row(self, req: Request) -> np.ndarray:
        row = np.full((self.serving.blocks_per_seq(),), -1, np.int32)
        row[:len(req.block_ids)] = req.block_ids
        return row

    def _sync_block_tables(self, rows: List[Tuple[int, np.ndarray]],
                           fresh_ids: List[int]) -> None:
        """Mirror host allocator decisions into both device caches: reset
        ``kv_pos`` of freshly (re)allocated blocks (a recycled block must
        never leak stale-but-causally-valid entries to its new owner) and
        rewrite the affected block-table rows."""
        if not rows and not fresh_ids:
            return
        with _span("engine.block_sync", rows=len(rows), fresh=len(fresh_ids)):
            st = self.state
            mirror = self.drafter.mirrors_kv()
            tc = dict(st.target_cache)
            dc = dict(st.draft_cache) if mirror else st.draft_cache
            if fresh_ids:
                tc["kv_pos"] = cache_lib.reset_blocks(tc["kv_pos"], fresh_ids)
                if mirror:
                    dc["kv_pos"] = cache_lib.reset_blocks(dc["kv_pos"],
                                                          fresh_ids)
            for slot, row in rows:
                r = jnp.asarray(row, jnp.int32)
                tc["block_table"] = tc["block_table"].at[slot].set(r)
                if mirror:
                    dc["block_table"] = dc["block_table"].at[slot].set(r)
            self.state = st._replace(target_cache=tc, draft_cache=dc)

    def _plan_blocks(self) -> None:
        """Pre-round capacity planning: grow every running sequence's
        allocation to cover the next round's write extent, preempting the
        youngest sequences (evict-and-requeue, recompute-on-readmit) when
        the pool runs dry instead of rejecting anybody.

        Synchronous mode plans exactly: ``committed +
        policy.lookahead(SL_i)``.  Pipelined mode plans from ONE-ROUND-
        STALE mirrors, so it must never trust a per-slot value that the
        in-flight round could raise; instead it uses the staleness-slack
        bound (DESIGN.md §7):

            need_i = cache_len_i(stale) + (1 + K_inflight) + (1 + K_next)

        where ``1 + K_inflight`` covers the largest commit the not yet
        reconciled round can apply and ``1 + K_next`` covers the next
        round's widest write (per-slot SL is capped by the bucket
        ``K_next`` on device, so the bound holds regardless of what the
        stale mirror says).  Lagged information can therefore only ever
        OVER-allocate — the tail comes back at the next shrink."""
        pipelined = self.serving.pipelined
        la = None if pipelined else self.scheduler.lookahead_slots()
        k_next = self._planned_k or 0
        inflight_ids = ({id(r) for r, _, _ in self._inflight.rows}
                        if self._inflight is not None else set())
        slot_of = {id(r): r.slot for r in self.scheduler.running}
        fresh_ids: List[int] = []
        rows: List[Tuple[int, np.ndarray]] = []
        cleared: List[Tuple[int, np.ndarray]] = []
        for req in sorted(self.scheduler.running, key=lambda r: r.admit_seq):
            if req.slot is None:        # preempted by an earlier grow
                continue
            if pipelined:
                slack = ((1 + self._inflight.k)
                         if id(req) in inflight_ids else 0)
                need = min(req.cache_len + slack + k_next + 1,
                           self.serving.max_seq_len)
            else:
                need = req.cache_len + int(la[req.slot])
            new_blocks, preempted = self.scheduler.ensure_capacity(req, need)
            if new_blocks:
                fresh_ids += new_blocks
                rows.append((req.slot, self._table_row(req)))
            for victim in preempted:
                cleared.append((slot_of[id(victim)],
                                np.full((self.serving.blocks_per_seq(),),
                                        -1, np.int32)))
        self._sync_block_tables(rows + cleared, fresh_ids)

    # --------------------------------------------------------------- prefill
    def _emit_token(self, req: Request, tok: int, now: float) -> None:
        """The single host-side token-delivery point: append to the
        request's output, stamp first-token latency, and fire the
        request's streaming callback (DESIGN.md §14).  Every reconciled
        token — prefill-sampled first tokens and round emissions alike —
        flows through here exactly once, in stream order, which is the
        whole streaming contract: consumers see the same byte sequence
        ``run()`` accumulates in ``Request.output``."""
        req.output.append(tok)
        self.emitted_total += 1
        if req.first_token_time is None:
            req.first_token_time = now
        if req.on_token is not None:
            req.on_token(req, tok)

    def _commit_first_tokens(self, items: List[Tuple[Request, int]],
                             now: float) -> List[Request]:
        """Append prefill-sampled first tokens host-side and apply the
        EOS / max_new_tokens terminal checks (the host mirror of the
        device-side ``done`` computation at prefill)."""
        finished = []
        for req, tok in items:
            self._emit_token(req, tok, now)
            if ((req.eos_token_id is not None and tok == req.eos_token_id)
                    or len(req.output) >= req.max_new_tokens):
                req.state = RequestState.FINISHED
                req.finish_time = now
                finished.append(req)
        return finished

    def _admit(self) -> None:
        """Admission: move queued requests into free slots and prefill
        them, grouped by prompt bucket — every same-bucket group runs as
        ONE multi-row program (2 jit calls per *group*, not per
        request)."""
        admitted = self.scheduler.admit()
        if not admitted:
            return
        now = time.monotonic()
        # warm (cache-hit) requests group by TAIL bucket — the program
        # their prefill actually runs — and separately from cold ones,
        # which stay on the cold entry point byte- and program-count-
        # identical with the pre-cache engine
        groups: Dict[Tuple[bool, int], List[Request]] = {}
        for req in admitted:
            if req.first_dispatch_time is None:
                req.first_dispatch_time = now
            warm = req.prefill_start > 0
            n = len(req.prefill_tokens()) - req.prefill_start
            b = _bucket(n, cap=self.serving.max_seq_len)
            groups.setdefault((warm, b), []).append(req)
        for warm, bucket in sorted(groups):
            reqs = groups[(warm, bucket)]
            with _span("engine.prefill", rows=len(reqs), bucket=bucket,
                       warm=warm):
                self._prefill_group(reqs, bucket, warm=warm)

    def _prefill_group(self, reqs: List[Request], bucket: int,
                       warm: bool = False) -> None:
        """One multi-row prefill program for a same-bucket group.

        Cold groups (``warm=False``) run the pre-cache entry points
        unchanged.  Warm groups (every row has ``prefill_start > 0``
        cached tokens) are bucketed by TAIL length and run the
        partial-prefix entry point: the tail program starts each row at
        its coverage offset, executes the group's batched copy-on-write
        block copies first, and only computes the uncovered suffix — the
        TTFT/FLOPs win prefix caching exists for (DESIGN.md §12)."""
        r = len(reqs)
        slots = [req.slot for req in reqs]
        idx = jnp.asarray(slots, jnp.int32)
        toks_np = np.zeros((r, bucket), np.int32)
        plens = np.zeros((r,), np.int32)
        starts = np.zeros((r,), np.int32)
        tails = np.zeros((r,), np.int32)
        readmit = np.zeros((r,), bool)
        budgets = np.zeros((r,), np.int32)
        eos = np.full((r,), -1, np.int32)
        pend_host = np.zeros((r,), np.int32)
        prefixes: List[List[int]] = []
        for i, req in enumerate(reqs):
            prefix = req.prefill_tokens()
            prefixes.append(prefix)
            start = req.prefill_start if warm else 0
            tail = prefix[start:]
            toks_np[i, :len(tail)] = tail      # cold: the full prefix
            plens[i] = len(prefix)
            starts[i] = start
            tails[i] = len(tail)
            # recompute-on-readmit (preemption): the last emitted token
            # IS the pending token; re-sampling would fork the RNG
            # stream and (at temperature > 0) the output
            readmit[i] = bool(req.output)
            # prefill itself emits one token for a fresh request
            budgets[i] = req.max_new_tokens - (len(req.output)
                                               if req.output else 1)
            if req.eos_token_id is not None:
                eos[i] = req.eos_token_id
            if req.output:
                pend_host[i] = req.output[-1]
            req.cache_len = len(prefix)
        self._prefill_tokens_pending += int(tails.sum())
        toks = jnp.asarray(toks_np)
        plen_j = jnp.asarray(plens)
        starts_j = jnp.asarray(starts)
        tails_j = jnp.asarray(tails)
        rows_j = None
        cow_src_j = cow_dst_j = None
        if warm:
            # <=1 COW pair per row by construction: only a full
            # block-aligned hit forks (the last shared block, whose final
            # position the tail recomputes).  Sentinel = pool size, the
            # write-drop discipline of cache_lib.copy_blocks.
            nb = self.scheduler.kv_blocks_total()
            cow_src = np.full((r,), nb, np.int32)
            cow_dst = np.full((r,), nb, np.int32)
            for i, req in enumerate(reqs):
                if req.cow_pairs:
                    cow_src[i], cow_dst[i] = req.cow_pairs[0]
            cow_src_j = jnp.asarray(cow_src)
            cow_dst_j = jnp.asarray(cow_dst)
        if self.paged:
            rows_np = [self._table_row(req) for req in reqs]
            # reset only PRIVATE fresh blocks: shared cache-hit blocks
            # hold live committed KV other sequences still read, and COW
            # destinations take their kv_pos from the device-side block
            # copy, which runs inside the tail program after this reset
            alloc_ids = [b for req in reqs for b in req.fresh_block_ids]
            self._sync_block_tables(list(zip(slots, rows_np)), alloc_ids)
            st = self.state
            tc = dict(st.target_cache)
            rows_j = jnp.asarray(np.stack(rows_np), jnp.int32)
            if warm:
                rows_t, last_t = prefill_lib.prefill_paged_tail(
                    self.pt, self.cfg_t, tc["k"], tc["v"], tc["kv_pos"],
                    rows_j, toks, starts_j, tails_j, cow_src_j, cow_dst_j,
                    plan=self._plan, k_scale=tc.get("k_scale"),
                    v_scale=tc.get("v_scale"))
            else:
                rows_t, last_t = prefill_lib.prefill_paged_rows(
                    self.pt, self.cfg_t, tc["k"], tc["v"], tc["kv_pos"],
                    rows_j, toks, plen_j, plan=self._plan,
                    k_scale=tc.get("k_scale"), v_scale=tc.get("v_scale"))
            tc = prefill_lib.scatter_paged_rows(tc, rows_t, idx)
        else:
            st = self.state
            rows_t, last_t = prefill_lib.prefill_rows(
                self.pt, self.cfg_t, toks, plen_j, self.serving.max_seq_len,
                plan=self._plan)
            tc = prefill_lib.set_slots(st.target_cache, rows_t, idx)
        # drafter-side prefill: a model drafter runs its own one-program-
        # per-bucket prefill (through the same jitted entry points, so
        # program accounting is symmetric); a model-free drafter absorbs
        # the tokens directly — no draft prefill program at all
        rows_mask = jnp.zeros((self.serving.max_batch_size,),
                              bool).at[idx].set(True)
        with _span("engine.prefill_draft"):
            dc = self.drafter.reset_rows(st.draft_cache, rows_mask)
            mirror_rows = (rows_j if (self.paged
                                      and self.drafter.mirrors_kv())
                           else None)
            if warm:
                # token-history drafters need the FULL prefix whatever
                # the KV coverage; mirroring drafters run the tail
                # program over their own pools and ignore it
                fbucket = _bucket(int(plens.max()),
                                  cap=self.serving.max_seq_len)
                full_np = np.zeros((r, fbucket), np.int32)
                for i, prefix in enumerate(prefixes):
                    full_np[i, :len(prefix)] = prefix
                dc = self.drafter.prefill_tail(
                    self.pd, dc, idx, jnp.asarray(full_np), plen_j,
                    toks, starts_j, tails_j, cow_src_j, cow_dst_j,
                    max_len=self.serving.max_seq_len,
                    table_rows=mirror_rows, plan=self._plan)
            else:
                dc = self.drafter.prefill(
                    self.pd, dc, idx, toks, plen_j,
                    max_len=self.serving.max_seq_len,
                    table_rows=mirror_rows,
                    plan=self._plan)
        # pending token per row: sampled at prefill for fresh requests
        # (per-request keys — schedule/grouping invariant), the
        # already-emitted last token for readmits
        req_keys = self._request_keys(reqs)
        sampled = jax.vmap(
            lambda kk, lg: sample_token(kk, lg, self.spec.temperature,
                                        self.cfg_t.vocab_size)
        )(req_keys, last_t).astype(jnp.int32)
        readmit_j = jnp.asarray(readmit)
        budgets_j = jnp.asarray(budgets)
        eos_j = jnp.asarray(eos)
        pend = jnp.where(readmit_j, jnp.asarray(pend_host), sampled)
        # device-side termination seed: a first token that is already EOS
        # (or a 1-token budget) marks the slot done WITHOUT a host sync,
        # so the pipelined loop can keep dispatching blind
        done0 = ((pend == eos_j) & (eos_j >= 0)) | (budgets_j <= 0)
        ps = self.policy.reset_rows(st.policy_state, rows_mask)
        sl0_val = self.policy.initial_sl_value()
        # refresh the scheduler's mirror too: block planning for this
        # round must see the fresh requests' initial SL, not the slots'
        # previous occupants' last predictions (a stale low SL would
        # under-allocate blocks and silently drop accepted KV writes)
        self._sl_next_host[np.asarray(slots)] = sl0_val
        self.scheduler.update_predictions(self._sl_next_host)
        # identity-threaded RNG rows: bind the slot to its new occupant's
        # seed and round ordinal (a readmit resumes its own key stream)
        seed_j = jnp.asarray([req.request_id for req in reqs], jnp.int32)
        ridx_j = jnp.asarray([req.rounds for req in reqs], jnp.int32)
        self.state = st._replace(
            target_cache=tc, draft_cache=dc, policy_state=ps,
            pending=st.pending.at[idx].set(pend),
            sl_next=st.sl_next.at[idx].set(jnp.int32(sl0_val)),
            seed=st.seed.at[idx].set(seed_j),
            round_idx=st.round_idx.at[idx].set(ridx_j),
            done=st.done.at[idx].set(done0),
            tokens_budget=st.tokens_budget.at[idx].set(budgets_j),
            eos_id=st.eos_id.at[idx].set(eos_j))
        for req in reqs:
            # COW sources are safe to reclaim once the copy is enqueued
            # (device program order), and the prompt's full blocks are
            # committed-by-enqueue too: publish them so the NEXT
            # admission wave can share them
            self.scheduler.release_cow_sources(req)
            req.fresh_block_ids = []
            req.cow_pairs = []
            self.scheduler.register_prefix(req)
        fresh = [(i, req) for i, req in enumerate(reqs) if not readmit[i]]
        if not fresh:
            return
        if self.serving.pipelined:
            # defer materialization: the tokens ride the next dispatch
            # record and reach the host at its reconciliation.  The
            # preemption count pins the prefill this token came from —
            # a stub whose request was evicted before the round even
            # dispatched is discarded at collect (the restart samples
            # its own first token from its own re-prefill)
            self._pending_admits.append(
                ([req for _, req in fresh], pend, [i for i, _ in fresh],
                 [req.preemptions for _, req in fresh]))
        else:
            pend_np = np.asarray(pend)
            fin = self._commit_first_tokens(
                [(req, int(pend_np[i])) for i, req in fresh],
                time.monotonic())
            for req in fin:    # finished at prefill (eos / max_new == 1)
                self.scheduler.release(req)
                self._finished_at_prefill.append(req)

    # ------------------------------------------------------------- the phases
    def plan(self) -> None:
        """Phase 1 — host-side planning from *reconciled* state (which in
        pipelined mode lags the device by one round): admission + batched
        prefill, the next round's bucket choice, and paged block growth
        under the staleness-slack invariant."""
        t_cpu = time.thread_time()
        with _span("engine.plan"):
            with _span("engine.admit"), jax.default_matmul_precision(
                    self.serving.matmul_precision):
                self._admit()
            self._planned_k = None
            if self.scheduler.running:
                if self.serving.pipelined:
                    with _span("engine.pick_bucket"):
                        self._planned_k = self._pick_bucket_pipelined()
                if self.paged:
                    before = self.scheduler.preempted_total
                    with _span("engine.plan_blocks"):
                        self._plan_blocks()   # may preempt (slots go inactive)
                    if (self.serving.pipelined and self.scheduler.running
                            and self.scheduler.preempted_total != before):
                        # an evicted slot must not size the bucket: re-pick
                        # over the survivors.  A smaller K only shrinks
                        # write extents, so the block growth just planned
                        # (with the wider K) still over-covers.
                        with _span("engine.pick_bucket"):
                            self._planned_k = self._pick_bucket_pipelined()
        self._plan_cpu_pending += time.thread_time() - t_cpu

    def _pick_bucket_pipelined(self) -> int:
        """Bucket choice for a pipelined dispatch, whose SL mirror is one
        round stale.  Greedy rounds pick from the stale mirror (a
        clipped window cannot change argmax streams).  Stochastic rounds
        dispatch at the policy's max bucket instead: a stale pick could
        clip a sequence's device-side SL below what the synchronous
        schedule runs, and at temperature>0 the realized sample stream
        depends on the proposal window — worst-case width keeps sampled
        streams schedule-invariant (DESIGN.md §7) at the cost of masked
        padding work."""
        if self.spec.temperature > 0.0:
            return self.policy.max_bucket()
        return self.policy.pick_bucket(self._host_context())

    def _host_context(self):
        """The round's :class:`HostRoundContext` for the policy host
        hooks — scheduler-owned per-slot state plus the engine's SL
        mirror, latency model, and round ordinal."""
        return self.scheduler.host_context(self._sl_next_host,
                                           round_ordinal=self.rounds)

    def dispatch(self) -> Optional[_DispatchRecord]:
        """Phase 2 — enqueue one speculative round.  Returns the dispatch
        record ``collect`` later reconciles, or None when no slot is
        occupied.  Never blocks on device results: the round's outputs
        stay futures, and their host copies are started asynchronously so
        they overlap the next round's compute."""
        if not self.scheduler.running:
            assert not self._pending_admits
            return None
        t_cpu = time.thread_time()
        rows = [(r, r.slot, r.preemptions) for r in self.scheduler.running]
        active_mask = self.scheduler.active_mask
        k = self._planned_k
        if k is None:
            with _span("engine.pick_bucket"):
                k = self.policy.pick_bucket(self._host_context())
        self._planned_k = None
        ordinal = self.rounds
        with _span("engine.dispatch", round=ordinal, k=k):
            t_dispatch = time.monotonic()
            with _span("engine.round_call"), jax.default_matmul_precision(
                    self.serving.matmul_precision):
                self.state, out = self._round_fn(k)(self.state,
                                                    jnp.asarray(active_mask))
                sl_next = self.state.sl_next
                for arr in (out.emitted, out.num_emitted, out.num_accepted,
                            out.num_proposed, out.finished, out.live,
                            sl_next):
                    arr.copy_to_host_async()
            self.rounds += 1
            self.draft_steps += (k + 1) if k > 0 else 0
            rec = _DispatchRecord(
                k=k, rows=rows, admits=self._pending_admits, out=out,
                sl_next=sl_next, t_dispatch=t_dispatch,
                prefill_tokens=self._prefill_tokens_pending, ordinal=ordinal,
                plan_cpu_s=self._plan_cpu_pending)
        self._prefill_tokens_pending = 0
        self._plan_cpu_pending = 0.0
        self._pending_admits = []
        self._inflight = rec
        rec.dispatch_cpu_s = time.thread_time() - t_cpu
        return rec

    def collect(self, rec: _DispatchRecord) -> List[Request]:
        """Phase 3 — reconcile a dispatched round: first block on its
        output transfer (already in flight since dispatch; the blocked
        interval is recorded per round), then mirror the device's
        decisions — token distribution, terminal states, SL mirror
        refresh, shrink-to-committed — on the host.  In pipelined mode
        this runs while the NEXT round is already executing, so shrink
        keeps the in-flight round's write extent resident."""
        t_cpu = time.thread_time()
        with _span("engine.collect", round=rec.ordinal, k=rec.k):
            t0 = time.monotonic()
            with _span("engine.collect_wait"):
                host = _RoundHost(
                    emitted=np.asarray(rec.out.emitted),
                    n_emit=np.asarray(rec.out.num_emitted),
                    n_acc=np.asarray(rec.out.num_accepted),
                    n_prop=np.asarray(rec.out.num_proposed),
                    fin=np.asarray(rec.out.finished),
                    live=np.asarray(rec.out.live),
                    sl_next=np.array(rec.sl_next),      # writable copy
                    admit_pends=[np.asarray(p) for _, p, _, _ in rec.admits])
            host_blocked = time.monotonic() - t0
            swept = self._verify_blocks_swept(rec) if self.paged else None
            with _span("engine.reconcile"):
                finished, shrunk_rows = self._reconcile(rec, host)
            if shrunk_rows:
                self._sync_block_tables(shrunk_rows, [])
            self._log_round(rec, host, host_blocked, t_cpu, swept)
        if self._inflight is rec:
            self._inflight = None
        return finished

    def _verify_blocks_swept(self, rec: _DispatchRecord) -> int:
        """Block-table columns the round's paged verify sweep read over
        the rows it carried: :func:`live_blocks` of each row's last query
        position, its committed length before the round plus K.  Called
        before the round's commit moves those lengths."""
        last = np.array([req.cache_len + rec.k for req, _, _ in rec.rows],
                        np.int64)
        return int(live_blocks(last, self.serving.kv_block_size,
                               self.serving.blocks_per_seq()).sum())

    def _reconcile(self, rec: _DispatchRecord, host: "_RoundHost"
                   ) -> Tuple[List[Request], List[Tuple[int, np.ndarray]]]:
        """Mirror a collected round's device decisions on the host; returns
        the requests it finished and the block-table rows its shrink
        changed."""
        emitted, n_emit, n_acc = host.emitted, host.n_emit, host.n_acc
        n_prop, fin, live = host.n_prop, host.fin, host.live
        # refresh the SL mirror only for slots STILL OWNED by the request
        # the round ran: a slot re-admitted at this iteration's plan (or
        # preempted) already carries its new occupant's initial SL, which
        # the dispatched round's snapshot — one occupant stale — must not
        # clobber
        for req, slot, _ in rec.rows:
            if self.scheduler.slots[slot] is req:
                self._sl_next_host[slot] = host.sl_next[slot]
        self.scheduler.update_predictions(self._sl_next_host)
        now = time.monotonic()
        finished: List[Request] = []
        # (a) first tokens from the prefill groups riding this record.
        # A stub whose request was preempted BEFORE this round was
        # dispatched (not in rec.rows) never ran on this prefill: drop
        # it, the readmission produces its own first token.  A request
        # preempted AFTER dispatch keeps the token (its round-emitted
        # tokens in step (b) follow it), and if the token finishes it
        # while it sits in the requeue it must be dropped from the
        # queue, not released — release would no-op on the empty slot
        # and the FINISHED request would be readmitted as a zombie.
        in_rows = {id(r) for r, _, _ in rec.rows}
        for (fresh_reqs, _, fresh_idx, pcounts), pend_np in zip(
                rec.admits, host.admit_pends):
            items = [(req, int(pend_np[i]), pc)
                     for req, i, pc in zip(fresh_reqs, fresh_idx, pcounts)
                     if id(req) in in_rows]
            for req in self._commit_first_tokens(
                    [(r, t) for r, t, _ in items], now):
                pc = next(p for r, _, p in items if r is req)
                if req.preemptions != pc or req.slot is None:
                    self.scheduler.drop_from_queue(req)
                else:
                    self.scheduler.release(req)
                finished.append(req)
        # (b) per-slot reconciliation against the dispatch-time snapshot
        # (the CURRENT slot table may already differ: collect runs after
        # the next plan, which can have preempted or re-admitted slots)
        inflight_k = (self._inflight.k
                      if (self._inflight is not None
                          and self._inflight is not rec) else None)
        shrunk_rows: List[Tuple[int, np.ndarray]] = []
        for req, slot, pcount in rec.rows:
            if req.done:
                continue       # reconciled to terminal by an earlier round
            # preempted (or re-admitted elsewhere) since dispatch: its
            # emitted tokens are real — the readmission prefix must
            # include them — but slot-side state (cache_len, blocks) was
            # reset by the eviction and must not be touched here
            displaced = req.preemptions != pcount or req.slot != slot
            if live[slot]:
                if not displaced:
                    req.cache_len += 1 + int(n_acc[slot])
                req.rounds += 1
                req.accepted_tokens += int(n_acc[slot])
                req.proposed_tokens += int(n_prop[slot])
                toks = emitted[slot, :n_emit[slot]].tolist()
                if req.first_token_time is None and toks:
                    req.first_token_time = now
                for t in toks:
                    if t == self.cfg_t.vocab_size:   # pad sentinel
                        continue
                    self._emit_token(req, int(t), now)
                if fin[slot]:
                    req.state = RequestState.FINISHED
                    req.finish_time = now
                if not displaced:
                    # decode extended the committed prefix: publish any
                    # newly completed full blocks.  Done BEFORE release so
                    # a finishing request's blocks drop to the evictable
                    # (warm) list still indexed — the cache survives its
                    # contributors.
                    self.scheduler.register_prefix(req)
            if req.done:
                if displaced:
                    # finished while sitting in the requeue: it must not
                    # be readmitted and recomputed
                    self.scheduler.drop_from_queue(req)
                else:
                    self.scheduler.release(req)      # frees its blocks too
                finished.append(req)
            elif not displaced and self.paged and req.slot is not None:
                # rollback is free: speculative-tail blocks beyond the
                # committed length go straight back to the pool.  The
                # device table row must drop the freed entries NOW: a
                # freed block can be reallocated at the next admission,
                # and a stale row entry would gather the new owner's
                # causally-valid KV into this sequence's attention.
                # With a round in flight, its write extent (committed +
                # K_inflight + 1) stays resident — those writes land in
                # device order whatever the host does, and the blocks
                # must still be this sequence's when they do.
                keep = (req.cache_len if inflight_k is None
                        else min(req.cache_len + inflight_k + 1,
                                 self.serving.max_seq_len))
                if self.scheduler.shrink_to(req, keep):
                    shrunk_rows.append((req.slot, self._table_row(req)))
        return finished, shrunk_rows

    def _log_round(self, rec: _DispatchRecord, host: "_RoundHost",
                   host_blocked: float, t_cpu: float,
                   swept: Optional[int]) -> None:
        """Append a collected round's record to the round log and feed the
        latency model.  ``t_cpu`` is the thread clock at collect's start;
        ``swept`` the paged verify sweep's blocks (None off the pool)."""
        n_emit, n_acc, n_prop, live = (host.n_emit, host.n_acc, host.n_prop,
                                       host.live)
        # (c) round log — emitted/accepted/proposed all masked by the
        # SAME per-round live-row set (slots that did real work), and
        # draft_steps_effective takes its max over that set too
        round_rec = {
            "round": rec.ordinal,
            "k": rec.k,
            "drafter": self.spec.drafter,
            "emitted": float(n_emit[live].sum()),
            "accepted": float(n_acc[live].sum()),
            "proposed": float(n_prop[live].sum()),
        }
        eff_steps = 0
        if rec.k > 0 and live.any():
            eff_steps = int(n_prop[live].max()) + 1
            self.draft_steps_effective += eff_steps
        # the round's effective draft depth, of the k + 1 steps dispatched
        round_rec["draft_steps_effective"] = float(eff_steps)
        # what this round's drafting actually cost, in target-
        # verification units — the capacity-vs-latency number that makes
        # model-free drafters' wins visible in benchmark rows
        round_rec["draft_cost_effective"] = (eff_steps
                                             * self.drafter.step_cost())
        round_rec["kv_blocks_in_use"] = float(
            self.scheduler.kv_blocks_in_use())
        round_rec["kv_pool_utilization"] = (
            round_rec["kv_blocks_in_use"]
            / max(self.scheduler.kv_blocks_total(), 1))
        if swept is not None:
            round_rec["verify_blocks_swept"] = float(swept)
        # draft-side KV residency: the mirrored pool holds exactly the
        # target's in-use block set; a model-free drafter holds none —
        # the capacity win of lookup/self drafting, made visible per round
        round_rec["draft_kv_blocks_in_use"] = (
            round_rec["kv_blocks_in_use"] if self.drafter.mirrors_kv()
            else 0.0)
        # prefix-cache deltas since the previous round's log entry
        # (admissions land between collects, so the deltas attribute each
        # wave's hits/copies to the round that carried it)
        sch = self.scheduler
        round_rec["kv_blocks_cached"] = float(sch.kv_blocks_cached())
        round_rec["prefix_cache_hit_blocks"] = float(
            sch.prefix_hit_blocks_total - self._hit_blocks_logged)
        self._hit_blocks_logged = sch.prefix_hit_blocks_total
        round_rec["cow_copies"] = float(
            sch.cow_copies_total - self._cow_logged)
        self._cow_logged = sch.cow_copies_total
        d_tok = sch.prefix_tokens_total - self._prefix_tok_logged
        d_hit = sch.prefix_hit_tokens_total - self._prefix_hit_tok_logged
        round_rec["prefix_cache_hit_rate"] = (d_hit / d_tok) if d_tok else 0.0
        self._prefix_tok_logged = sch.prefix_tokens_total
        self._prefix_hit_tok_logged = sch.prefix_hit_tokens_total
        round_rec["host_blocked_s"] = host_blocked
        # per-round cadence: with a successor round already in flight,
        # dispatch-to-dispatch (so pipelined per-round walls sum to the
        # run wall instead of double-counting the overlapped round);
        # otherwise — sync, or the drain of the last round — dispatch to
        # reconciliation end, the full lockstep round cost
        if self._inflight is not None and self._inflight is not rec:
            round_rec["wall_s"] = self._inflight.t_dispatch - rec.t_dispatch
        else:
            round_rec["wall_s"] = time.monotonic() - rec.t_dispatch
        # the latency model's regressors, then one RLS sample per round
        # (DESIGN.md §15)
        b_eff = len(rec.rows)
        round_rec["b_eff"] = float(b_eff)
        round_rec["prefill_tokens"] = float(rec.prefill_tokens)
        self.latency_model.observe(round_rec["wall_s"], rec.k, b_eff,
                                   rec.prefill_tokens)
        # thread CPU seconds of the three phases that served this round
        round_rec["plan_cpu_s"] = rec.plan_cpu_s
        round_rec["dispatch_cpu_s"] = rec.dispatch_cpu_s
        round_rec["collect_cpu_s"] = time.thread_time() - t_cpu
        self.round_log.append(round_rec)

    # ------------------------------------------------------------------ step
    def step(self) -> List[Request]:
        """Synchronous lockstep: plan, dispatch, collect — the round is
        fully reconciled before control returns.  Returns requests that
        reached a terminal state this step (finished OR rejected-at-
        admission)."""
        self.plan()
        done_early = self._finished_at_prefill + self.scheduler.pop_rejected()
        self._finished_at_prefill = []
        if not self.scheduler.running:
            return done_early
        rec = self.dispatch()
        return done_early + self.collect(rec)

    # ------------------------------------------------------------------ pump
    def has_pending_work(self) -> bool:
        """True while the engine still owes work: queued or running
        requests, or (pipelined) a dispatched round awaiting its
        reconciliation.  The front-end's driver loop (DESIGN.md §14)
        polls this between ``pump()`` iterations."""
        return self.scheduler.has_work() or self._inflight is not None

    def pump(self) -> List[Request]:
        """One driver-loop iteration — exactly ``run()``'s loop body, so
        an external driver that interleaves ``submit()`` between pumps
        replays the same admit/dispatch/collect sequence (and therefore,
        with arrival-time-0 submissions, the same streams) ``run()``
        produces.  Sync mode is one lockstep ``step()``; pipelined mode
        plans + dispatches round N+1, then reconciles round N while N+1
        executes on device.  Returns requests that reached a terminal
        state this iteration; when ``has_pending_work()`` goes false the
        driver must ``drain()`` the final in-flight round.  The iteration
        is the profiler step ``engine.round``, numbered by the ordinal of
        the round it dispatches."""
        with jax.profiler.StepTraceAnnotation("engine.round",
                                              step_num=self.rounds):
            if not self.serving.pipelined:
                return self.step() if self.scheduler.has_work() else []
            done: List[Request] = []
            self.plan()
            done += self.scheduler.pop_rejected()
            prev = self._inflight
            self.dispatch()
            if prev is not None:
                done += self.collect(prev)
            return done

    def drain(self) -> List[Request]:
        """Reconcile the last in-flight round after the final ``pump()``
        (pipelined mode dispatches one round ahead of reconciliation).
        No-op in sync mode or when nothing is in flight."""
        if self._inflight is not None:
            return self.collect(self._inflight)
        return []

    # ------------------------------------------------------------------- run
    def run(self, requests: Sequence[Request],
            max_rounds: Optional[int] = None) -> Dict[str, float]:
        t0 = time.monotonic()
        for r in requests:
            self.submit(r)
        done: List[Request] = []
        # pipelined: plan(N+1) → dispatch(N+1) → collect(N), the host
        # reconciling one round behind while the device never waits
        while self.has_pending_work():
            done += self.pump()
            if max_rounds is not None and self.rounds >= max_rounds:
                break
        done += self.drain()
        wall = time.monotonic() - t0
        return self.summary(done, wall)

    def summary(self, done: Sequence[Request],
                wall: float) -> Dict[str, float]:
        """Run-level metrics over a set of terminal requests — shared by
        ``run()`` and any external driver (the serving front-end) so a
        ``pump()``-driven session reports through the same lens."""
        fin = [r for r in done if r.state == RequestState.FINISHED]
        rej = [r for r in done if r.state == RequestState.REJECTED]
        lat = [r.latency() for r in fin if r.latency() is not None]
        ttft = [r.ttft() for r in fin if r.ttft() is not None]
        qw = [r.queue_wait() for r in fin if r.queue_wait() is not None]
        blocked = float(sum(r.get("host_blocked_s", 0.0)
                            for r in self.round_log))
        # SLO accounting (DESIGN.md §15): attainment over every terminal
        # request (a rejected request never attains); goodput counts only
        # tokens of requests that met their own deadline.  With no
        # deadlines anywhere every finished request attains, so
        # slo_goodput_tok_s == throughput_tok_s.
        attained = [r for r in done if r.slo_attained()]
        slo = {
            "slo_requests_attained": len(attained),
            "slo_attained_frac": len(attained) / max(len(done), 1),
            "slo_goodput_tok_s": (sum(len(r.output) for r in attained)
                                  / max(wall, 1e-9)),
            "slo_predicted_violations": float(
                self.scheduler.slo_predicted_violations),
            "slo_deferrals": float(self.scheduler.slo_deferrals_total),
        }
        return {
            **self.latency_model.summary_fields(),
            **slo,
            "wall_time_s": wall,
            "requests_finished": len(fin),
            "requests_rejected": len(rej),
            "preemptions": self.scheduler.preempted_total,
            "tokens_emitted": self.emitted_total,
            "rounds": self.rounds,
            "drafter": self.spec.drafter,
            "draft_step_cost": self.drafter.step_cost(),
            "draft_cost_effective": float(sum(
                r.get("draft_cost_effective", 0.0) for r in self.round_log)),
            "draft_kv_blocks_peak": float(max(
                (r.get("draft_kv_blocks_in_use", 0.0)
                 for r in self.round_log), default=0.0)),
            "draft_steps": self.draft_steps,
            "draft_steps_effective": self.draft_steps_effective,
            # paper's BE: tokens per target verification, per sequence
            "block_efficiency": float(np.mean(
                [r.block_efficiency() for r in fin])) if fin else float("nan"),
            "batch_tokens_per_round": self.emitted_total / max(self.rounds, 1),
            "throughput_tok_s": self.emitted_total / max(wall, 1e-9),
            "mean_latency_s": float(np.mean(lat)) if lat else float("nan"),
            "p95_latency_s": float(np.percentile(lat, 95)) if lat else float("nan"),
            # serving-side metrics the paper's §5 tables are framed
            # around: time-to-first-token and scheduler queue wait
            "ttft_mean_s": float(np.mean(ttft)) if ttft else float("nan"),
            "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft else float("nan"),
            "queue_wait_mean_s": float(np.mean(qw)) if qw else float("nan"),
            # host time spent blocked on device output transfers — the
            # pipeline's figure of merit (benchmarks/table6)
            "host_blocked_s": blocked,
            "host_blocked_per_round_s": blocked / max(len(self.round_log), 1),
            "mean_acceptance": float(np.mean(
                [r.acceptance_rate() for r in fin])) if fin else float("nan"),
            "kv_blocks_peak": float(max(
                (r["kv_blocks_in_use"] for r in self.round_log),
                default=0.0)),
            "kv_pool_blocks": float(self.scheduler.kv_blocks_total()),
            # storage-plane telemetry (DESIGN.md §13): bytes, not blocks,
            # are what an int8 pool halves at equal block count
            "kv_quant": self.kv_quant,
            "kv_block_bytes": float(self.scheduler.kv_block_bytes()),
            "kv_pool_bytes": float(self.scheduler.kv_bytes_total()),
            # resident KV bytes integrated over rounds — a proxy for the
            # bytes the verify kv-sweeps stream from the pool, the
            # quantity int8 storage actually cuts (benchmarks/table9)
            "kv_bytes_swept": float(sum(
                r["kv_blocks_in_use"] for r in self.round_log))
                * float(self.scheduler.kv_block_bytes()),
            # pool-pressure aggregates + prefix-cache lifetime telemetry
            # (satellite of DESIGN.md §12): hit rate is token-weighted
            # over every (re)admission prefill the run performed
            "kv_pool_utilization_mean": (float(np.mean(
                [r["kv_pool_utilization"] for r in self.round_log]))
                if self.round_log else 0.0),
            "kv_pool_utilization_peak": float(max(
                (r["kv_pool_utilization"] for r in self.round_log),
                default=0.0)),
            "prefix_cache_hit_blocks": float(
                self.scheduler.prefix_hit_blocks_total),
            "prefix_cache_hit_rate": (
                self.scheduler.prefix_hit_tokens_total
                / max(self.scheduler.prefix_tokens_total, 1)),
            "cow_copies": float(self.scheduler.cow_copies_total),
            "prefix_cache_evictions": float(
                self.scheduler.allocator.evictions
                if self.scheduler.allocator is not None else 0.0),
        }
