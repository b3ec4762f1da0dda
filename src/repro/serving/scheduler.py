"""Look-ahead scheduler (paper §3.2) over a block-budget data plane.

Computes per-sequence look-ahead KV slots directly from ``SL_i^(t)`` and is
applied uniformly to prefill and decode admission — the vLLM modification
the paper describes ("removes inconsistencies between feasibility checks
and append operations and aligns capacity planning with intra-batch
heterogeneity").

Capacity planning is policy-owned on both horizons:

* **feasibility** — a request whose worst case (``prompt + max_new_tokens
  + policy.max_lookahead()``) cannot fit ``max_seq_len`` is terminally
  ``REJECTED`` (surfaced through ``pop_rejected``), never silently
  dropped;
* **per-round planning** exposes ``SpecPolicy.lookahead`` over the live
  per-sequence SL predictions the engine mirrors to the host each round
  (``lookahead_slots``).

Two admission regimes share that planning:

* **dense** (``paged_kv=False``) — one max_seq_len KV row per slot;
  admission is worst-case reservation: a free slot IS the budget.
* **paged** (``paged_kv=True``) — a :class:`BlockAllocator` owns a free
  list over the shared block pool.  Admission charges only the blocks the
  prefill actually needs, and admits only while two free blocks remain
  for every running sequence; each round the engine asks
  :meth:`ensure_capacity` to grow a sequence to ``committed + SL_i + 1``
  tokens (``policy.lookahead``), and when the pool runs dry the youngest
  running request is **preempted** — its blocks return to the pool and it
  is requeued at the front for recompute-on-readmit — instead of anybody
  being rejected.  After each round the engine returns the speculative
  tail blocks via :meth:`shrink_to` (rollback stays free length
  arithmetic).  The pool must hold at least one max-length sequence
  (asserted), which guarantees preemption always converges.

The scheduler owns: the waiting queue, the slot table, the block
allocator, and both admission decisions.

Under the pipelined engine (DESIGN.md §7) every scheduler decision is
made from state that may be ONE ROUND STALE: plan(N+1) runs before
round N is reconciled, so slots freed by round N become visible one
iteration later and per-sequence ``cache_len``/SL mirrors lag by one
round.  Admission and preemption are safe under that lag by
construction — a slot is only handed out after its previous occupant
was host-reconciled and released, and the engine's block planning adds
the worst-case in-flight slack (see ``ServingEngine._plan_blocks``) so
stale mirrors can only ever OVER-allocate, never under-allocate.
"""
from __future__ import annotations

import collections
import math
import time
from typing import Any, List, Optional, Tuple

import numpy as np

from repro.core.config import ServingConfig, SpecDecodeConfig
from repro.core.policies import HostRoundContext, SpecPolicy, build_policy
from repro.serving.request import Request, RequestState


class BlockAllocator:
    """Refcounted free-list allocator over the shared KV block pool.

    Block ids are logical handles: id ``i`` names slot ``i`` of *both*
    the target and draft pools (the block tables mirror), so one
    allocation decision covers the whole speculative pair.

    Prefix caching (DESIGN.md §4/§12) layers three structures on top of
    the plain free list:

    * ``refcount[b]`` — how many block tables reference physical block
      ``b``.  :meth:`alloc` hands out blocks at refcount 1,
      :meth:`acquire` maps an already-resident block into another
      sequence (incref), and :meth:`free` is a *decref* — a block only
      leaves circulation when its last reference drops.
    * a content-hash index over committed **full** blocks: each
      registered block stores ``(parent_hash, block_tokens)`` and is
      addressed by the chained hash of that pair, so a prefix match is
      a walk down the chain.  Stored tokens are compared on lookup —
      a hash collision degrades to a cache miss, never a wrong block.
    * an LRU *evictable* list: registered blocks whose refcount drops
      to 0 stay warm (still index-addressable, revivable by
      :meth:`acquire`) and are reclaimed oldest-first only when
      :meth:`alloc` finds the free list short.  Unregistered blocks
      return straight to the free list as before.

    Pool accounting invariant (property-tested):
    ``free + evictable + |{b : refcount[b] > 0}| == num_blocks`` with
    the three sets pairwise disjoint.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 block_bytes: int = 0):
        assert num_blocks > 0 and block_size > 0
        self.num_blocks = num_blocks
        self.block_size = block_size
        # bytes one physical block costs in the backing pool (dtype- and
        # quant-mode-aware, cache_lib.kv_block_bytes).  Blocks stay the
        # allocation unit — bytes are telemetry: an int8 pool admits the
        # same block count at under half the bytes (DESIGN.md §13).
        self.block_bytes = block_bytes
        # LIFO free list, seeded so the first allocations come out in
        # ascending id order (pleasant for debugging, irrelevant for
        # correctness — the block table indirection absorbs any order)
        self._free = list(range(num_blocks - 1, -1, -1))
        self.refcount = [0] * num_blocks
        # chain_hash -> block id holding that prefix block
        self._index: dict = {}
        # block id -> (parent_hash, tokens_tuple, chain_hash)
        self._meta: dict = {}
        # unreferenced-but-registered blocks, insertion order = LRU
        # (oldest first; revived blocks re-enter at the recent end)
        self._evictable: "collections.OrderedDict[int, None]" = (
            collections.OrderedDict())
        self.evictions = 0

    @property
    def n_free(self) -> int:
        """Allocatable blocks: truly free plus warm evictable."""
        return len(self._free) + len(self._evictable)

    @property
    def n_used(self) -> int:
        """Blocks referenced by at least one block table."""
        return self.num_blocks - self.n_free

    @property
    def n_cached(self) -> int:
        """Warm unreferenced blocks held for prefix reuse."""
        return len(self._evictable)

    @property
    def bytes_total(self) -> int:
        """Pool footprint in bytes (0 when the caller never sized it)."""
        return self.num_blocks * self.block_bytes

    @property
    def bytes_in_use(self) -> int:
        return self.n_used * self.block_bytes

    def blocks_for(self, n_tokens: int) -> int:
        return max(0, -(-n_tokens // self.block_size))

    # ------------------------------------------------------------ hash chain
    @staticmethod
    def _chain_hash(parent_hash: Optional[int],
                    tokens: Tuple[int, ...]) -> int:
        # int-tuple hashing is deterministic within a process, which is
        # all the host-side index needs (nothing device-visible).
        return hash((parent_hash, tokens))

    def match_prefix(self, tokens) -> Tuple[List[int], Optional[int], int]:
        """Walk ``tokens`` down the hash chain over full blocks.

        Returns ``(block_ids, last_chain_hash, covered_tokens)`` for the
        longest cached prefix.  Does NOT take references — callers pair
        it with :meth:`acquire` once admission is certain."""
        ids: List[int] = []
        parent: Optional[int] = None
        bs = self.block_size
        for i in range(len(tokens) // bs):
            chunk = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            h = self._chain_hash(parent, chunk)
            bid = self._index.get(h)
            if bid is None:
                break
            meta = self._meta[bid]
            if meta[0] != parent or meta[1] != chunk:
                break                       # collision: treat as a miss
            ids.append(bid)
            parent = h
        return ids, parent, len(ids) * bs

    def register(self, block_id: int, parent_hash: Optional[int],
                 tokens: Tuple[int, ...]) -> int:
        """Publish a committed full block under its chain hash.

        First writer wins: if the hash is already indexed (another
        sequence committed the same prefix first) the caller keeps its
        private copy unshared and future matches converge on the
        canonical block.  Returns the chain hash either way so callers
        can thread it as the next block's parent."""
        assert self.refcount[block_id] > 0, "registering an unowned block"
        h = self._chain_hash(parent_hash, tokens)
        if h not in self._index and block_id not in self._meta:
            self._index[h] = block_id
            self._meta[block_id] = (parent_hash, tokens, h)
        return h

    def _unregister(self, block_id: int) -> None:
        meta = self._meta.pop(block_id, None)
        if meta is not None and self._index.get(meta[2]) == block_id:
            del self._index[meta[2]]

    # ------------------------------------------------------------ lifecycle
    def alloc(self, n: int) -> Optional[List[int]]:
        """n private blocks at refcount 1, or None (and no state change)
        if free + evictable cannot cover the ask.  Evicts warm cached
        blocks oldest-first only under pressure — a hit on a block that
        was never evicted costs nothing."""
        if n > len(self._free) + len(self._evictable):
            return None
        if n <= 0:
            return []
        while len(self._free) < n:
            bid, _ = self._evictable.popitem(last=False)     # LRU oldest
            self._unregister(bid)
            self._free.append(bid)
            self.evictions += 1
        out = self._free[-n:][::-1]
        del self._free[-n:]
        for b in out:
            assert self.refcount[b] == 0
            self.refcount[b] = 1
        return out

    def acquire(self, blocks: List[int]) -> None:
        """Map already-resident blocks into one more block table
        (incref), reviving warm evictable blocks in place."""
        for b in blocks:
            if self.refcount[b] == 0:
                self._evictable.pop(b)      # registered + warm, by invariant
            self.refcount[b] += 1

    def free(self, blocks: List[int]) -> None:
        """Decref.  A block leaves circulation only at refcount 0:
        registered blocks stay warm on the evictable LRU (recent end),
        unregistered blocks return to the free list."""
        for b in blocks:
            assert self.refcount[b] > 0, "double free"
            self.refcount[b] -= 1
            if self.refcount[b] == 0:
                meta = self._meta.get(b)
                if meta is not None and self._index.get(meta[2]) == b:
                    self._evictable[b] = None
                else:
                    self._unregister(b)
                    self._free.append(b)
        assert len(self._free) + len(self._evictable) <= self.num_blocks

    def fork_cow(self, block_id: int) -> Optional[int]:
        """Copy-on-write split: allocate a private destination block and
        drop this table's reference on the shared source.  Returns the
        destination id (caller schedules the device-side block copy) or
        None if the pool cannot cover it.  The source is safe from the
        eviction inside :meth:`alloc` because the caller still holds its
        reference until the :meth:`free` below."""
        dst = self.alloc(1)
        if dst is None:
            return None
        self.free([block_id])
        return dst[0]

    def check_invariants(self) -> None:
        """Property-test hook: free/evictable/referenced partition the
        pool and no block is simultaneously free and referenced."""
        free = set(self._free)
        warm = set(self._evictable)
        ref = {b for b in range(self.num_blocks) if self.refcount[b] > 0}
        assert len(free) == len(self._free), "duplicate ids on free list"
        assert not (free & ref), "block simultaneously free and referenced"
        assert not (warm & ref), "block simultaneously warm and referenced"
        assert not (free & warm), "block simultaneously free and warm"
        assert len(free) + len(warm) + len(ref) == self.num_blocks
        for b in warm:
            meta = self._meta.get(b)
            assert meta is not None and self._index.get(meta[2]) == b, (
                "evictable block not reachable from the hash index")


class LookaheadScheduler:
    def __init__(self, serving: ServingConfig, spec: SpecDecodeConfig,
                 policy: Optional[SpecPolicy] = None,
                 kv_mirror: bool = True,
                 prefix_cache: Optional[bool] = None,
                 block_bytes: int = 0):
        """``kv_mirror``: whether the serving drafter holds a paged KV
        pool mirroring the target's block ids (``Drafter.mirrors_kv``).
        ``ServingConfig.num_kv_blocks`` budgets such a mirrored *pair*;
        a drafter with no draft-side KV halves the per-sequence charge,
        so its whole mirror budget returns to the target pool — the pool
        doubles and admits proportionally more in-flight sequences
        (DESIGN.md §9).

        ``prefix_cache`` overrides ``serving.prefix_caching`` — the
        engine passes the *effective* flag after gating on model-family
        support (recurrent per-slot state cannot be recovered from the
        block pool, DESIGN.md §12).

        ``block_bytes``: bytes one pool block costs under the serving
        cache's dtype/quant mode (``cache_lib.kv_block_bytes``); the
        engine sources it from the target config.  Purely telemetry —
        admission stays block-denominated — but it is what makes the
        ``kv_pool_bytes`` metrics honest across fp and int8 pools."""
        self.serving = serving
        self.spec = spec
        self.policy = policy if policy is not None else build_policy(spec)
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: List[Optional[Request]] = [None] * serving.max_batch_size
        self.allocator: Optional[BlockAllocator] = None
        self.prefix_cache = bool(
            serving.prefix_caching if prefix_cache is None else prefix_cache)
        self.prefix_cache = self.prefix_cache and serving.paged_kv
        if serving.paged_kv:
            pool = serving.pool_blocks() * (1 if kv_mirror else 2)
            self.allocator = BlockAllocator(pool, serving.kv_block_size,
                                            block_bytes=block_bytes)
            # Without prefix caching the pool must hold one max-length
            # sequence outright, so LIFO preemption always converges.
            # With it, smaller pools are admissible: the pool-feasibility
            # term of _fits rejects requests that could never be
            # resident, and ensure_capacity self-preempts (warm readmit
            # through the cache) instead of asserting.
            assert self.prefix_cache or (
                self.allocator.num_blocks * self.allocator.block_size
                >= serving.max_seq_len), (
                "KV pool smaller than one max-length sequence — "
                "preemption could never free enough blocks")
        self.block_bytes = block_bytes
        # latest per-slot SL predictions (host mirror, engine-refreshed)
        self.sl_pred = np.full((serving.max_batch_size,),
                               self.policy.initial_sl_value(), np.int32)
        self._rejected: List[Request] = []
        self._admit_seq = 0
        self.preempted_total = 0
        # SLO-aware admission (DESIGN.md §15): the engine installs its
        # RoundLatencyModel here; without one (or before it is ready)
        # admission is deadline-blind, exactly the pre-SLO behaviour.
        self.latency_model: Optional[Any] = None
        self._slo_risk: List[Request] = []
        self.slo_predicted_violations = 0
        self.slo_deferrals_total = 0
        # lifetime prefix-cache telemetry (engine aggregates per-round)
        self.prefix_hit_blocks_total = 0
        self.cow_copies_total = 0
        self.prefix_tokens_total = 0
        self.prefix_hit_tokens_total = 0

    def _caching(self) -> bool:
        return self.allocator is not None and self.prefix_cache

    # ------------------------------------------------------------- admission
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def update_predictions(self, sl_next: np.ndarray) -> None:
        """Engine hook: refresh the host mirror of per-sequence SL
        predictions after each round (copied — the scheduler owns its
        mirror, never aliasing the engine's)."""
        self.sl_pred = np.array(sl_next)

    def host_context(self, sl_next: Optional[np.ndarray] = None,
                     round_ordinal: int = 0,
                     now: Optional[float] = None) -> HostRoundContext:
        """Build the round's :class:`HostRoundContext` — the host-side
        batch-global view handed to the policy hooks.  Per-slot
        deadline-remaining and token budgets come from the slot table
        (``+inf`` / 0 for empty or deadline-free slots); the latency
        model is whatever the engine installed.  Everything is host
        state the scheduler already owns — no device sync."""
        sl = self.sl_pred if sl_next is None else np.asarray(sl_next)
        b = self.serving.max_batch_size
        deadlines = np.full((b,), np.inf)
        tokens_rem = np.zeros((b,), np.int64)
        if any(r is not None and r.slo_deadline_s is not None
               for r in self.slots):
            now = time.monotonic() if now is None else now
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            tokens_rem[i] = max(r.max_new_tokens - len(r.output), 0)
            if r.slo_deadline_s is not None:
                deadlines[i] = (r.arrival_time + r.slo_deadline_s) - now
        return HostRoundContext(
            sl_next=sl, active=self.active_mask,
            deadline_remaining_s=deadlines, tokens_remaining=tokens_rem,
            latency_model=self.latency_model, round_ordinal=round_ordinal)

    def lookahead_slots(self, sl_next: Optional[np.ndarray] = None
                        ) -> np.ndarray:
        """KV slots each sequence needs next round, per the policy."""
        return self.policy.lookahead(self.host_context(sl_next))

    def _fits(self, req: Request, covered_blocks: int = 0) -> bool:
        # feasibility must cover the policy's WORST-case round footprint:
        # a dynamic policy admitted at its initial SL can later predict up
        # to its max, and the verification write would overrun the budget
        need = (len(req.prompt) + req.max_new_tokens
                + self.policy.max_lookahead())
        if need > self.serving.max_seq_len:
            return False
        if self.allocator is not None:
            # Pool-feasibility: a request whose worst-case block
            # residency can never fit the pool would preempt-requeue
            # forever — reject it up front.  Cached-prefix coverage
            # discounts the ask: covered blocks are already resident
            # (paid for by the cache, shareable across requesters), so
            # only the uncovered suffix must come out of the pool.  A
            # request that fits only BECAUSE of cache hits admits.
            # Legacy configs (no prefix cache) are unaffected: the init
            # assert pins pool >= max_seq_len there, so the max_seq_len
            # term above already subsumes this one.
            uncovered = self.allocator.blocks_for(need) - covered_blocks
            if uncovered > self.allocator.num_blocks:
                return False
        return True

    def _leaves_headroom(self, need: int) -> bool:
        """Whether taking ``need`` blocks leaves two free blocks for every
        running sequence: growth room for the whole batch over ``2 *
        block_size`` tokens a row before any sequence has to finish.
        Admitting into a pool with less hands the next rounds' growth to
        preemption, and LIFO preemption then evicts the sequence just
        admitted and recomputes it, round after round.  With nothing
        running the whole pool is available."""
        running = sum(r is not None for r in self.slots)
        return self.allocator.n_free - need >= 2 * running

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def _is_readmit(self, req: Request) -> bool:
        """A queued request that has run before (evict-and-requeue)."""
        return req.preemptions > 0 or req.admit_time is not None

    def assert_readmit_fifo(self) -> None:
        """Starvation guard: preempted readmits form a contiguous PREFIX
        of the queue, ahead of every fresh arrival — a preempted request
        always readmits before new work is started, so sustained arrival
        pressure can delay but never starve in-flight requests.

        Holds by construction — fresh arrivals only ever ``append``
        (:meth:`submit`), readmits only ever ``appendleft``
        (:meth:`preempt`), and requests leave the queue strictly from
        the front — but the assert pins it against future scheduler
        edits.  Tie-break among the readmits themselves: within one
        preemption wave :meth:`ensure_capacity` picks victims
        youngest-first (LIFO by ``admit_seq``) and each ``appendleft``
        reverses that, so the wave lands oldest-admission-first — FIFO
        in admission order; across waves the most recent wave sits in
        front (the recompute-on-readmit stack discipline
        :meth:`preempt` documents)."""
        seen_fresh = False
        for r in self.queue:
            if self._is_readmit(r):
                assert not seen_fresh, (
                    "readmit queued behind a fresh arrival — starvation")
            else:
                seen_fresh = True

    # ------------------------------------------------------- SLO admission
    def predict_completion_s(self, req: Request) -> Optional[float]:
        """Best-case predicted wall seconds for ``req`` to finish once
        admitted, from the analytic latency model (DESIGN.md §15): its
        prefill cost plus ``ceil(tokens_remaining / (K+1))`` rounds at
        the policy's typical bucket against the current live batch.
        Best-case (every draft position accepted) by design — admission
        only flags requests that cannot attain their deadline *even if
        everything goes right*, so feasible requests are never gated on
        a pessimistic guess.  None when no model is installed/ready."""
        lm = self.latency_model
        if lm is None or not lm.ready():
            return None
        k = int(min(max(self.policy.initial_sl_value(), self.spec.sl_min)
                    if self.policy.uses_draft() else 0,
                    self.policy.max_bucket()))
        b_eff = min(len(self.running) + 1, self.serving.max_batch_size)
        tokens = max(req.max_new_tokens - len(req.output), 1)
        rounds = math.ceil(tokens / float(k + 1))
        return (lm.predict_prefill_s(len(req.prefill_tokens()))
                + rounds * lm.predict_round_s(k, b_eff))

    def _surface_slo_risk(self, req: Request) -> None:
        if not req.slo_predicted_violation:
            req.slo_predicted_violation = True
            self.slo_predicted_violations += 1
            self._slo_risk.append(req)

    def _slo_feasible_behind(self, head: Request, now: float) -> bool:
        """Is there a later FRESH request (same or higher priority) that
        is predicted to attain its deadline?  Only then is deferring the
        head worth anything — otherwise it admits in order."""
        for r in list(self.queue)[1:]:
            if self._is_readmit(r) or r.priority < head.priority:
                continue
            if r.slo_deadline_s is None:
                return True
            t = self.predict_completion_s(r)
            if t is None or now + t <= r.arrival_time + r.slo_deadline_s:
                return True
        return False

    def pop_slo_risk(self) -> List[Request]:
        """Drain requests newly flagged as predicted SLO violations
        (surfaced exactly once each; the flag stays on the request)."""
        out, self._slo_risk = self._slo_risk, []
        return out

    def admit(self) -> List[Request]:
        """Move queued requests into free slots (continuous batching).

        Dense: a free slot is a full max_seq_len reservation.  Paged: the
        request is also charged ``ceil(prefill_len / block_size)`` pool
        blocks up front; if the pool cannot cover the next request's
        prefill and still keep two free blocks for every running sequence
        (:meth:`_leaves_headroom`) it stays queued (preemption during the
        round, not admission, resolves sustained pressure).  Infeasible
        (oversize) requests become ``REJECTED`` and are drained via
        :meth:`pop_rejected`.

        SLO gate (DESIGN.md §15): alongside the block-budget ``_fits``
        check, a fresh deadline-carrying head whose *best-case*
        predicted completion already breaches its deadline is surfaced
        (:meth:`pop_slo_risk`) and — at most ``slo_defer_limit`` times,
        and only when a feasible same-or-higher-priority fresh arrival
        waits behind it — rotated to the back so attainable work is not
        burned behind a lost cause.  It is never rejected or dropped:
        past the limit (or with nothing feasible behind it) it admits in
        order, flagged.  Readmits are never deferred, and with no
        deadlines in the queue this path is inert, so admission order is
        exactly the pre-SLO order.

        Ordering: strict queue order, and :meth:`assert_readmit_fifo`
        pins the starvation guard — preempted readmits sit ahead of
        every fresh arrival, FIFO among themselves."""
        if __debug__:
            self.assert_readmit_fifo()
        admitted = []
        free = collections.deque(self.free_slots())
        deferred_ids: set = set()
        now = None
        while free and self.queue:
            req = self.queue[0]
            if (req.slo_deadline_s is not None
                    and not self._is_readmit(req)
                    and self.latency_model is not None
                    and self.latency_model.ready()):
                now = time.monotonic() if now is None else now
                t_pred = self.predict_completion_s(req)
                if (t_pred is not None and
                        now + t_pred > req.arrival_time + req.slo_deadline_s):
                    self._surface_slo_risk(req)
                    if (id(req) not in deferred_ids
                            and req.slo_deferrals < self.serving.slo_defer_limit
                            and self._slo_feasible_behind(req, now)):
                        self.queue.popleft()
                        self.queue.append(req)
                        req.slo_deferrals += 1
                        self.slo_deferrals_total += 1
                        deferred_ids.add(id(req))
                        continue
            toks = req.prefill_tokens()
            plen = len(toks)
            covered_ids: List[int] = []
            last_hash: Optional[int] = None
            covered = 0
            if self._caching():
                covered_ids, last_hash, covered = (
                    self.allocator.match_prefix(toks))
            if not self._fits(req, covered_blocks=len(covered_ids)):
                self.queue.popleft()
                req.state = RequestState.REJECTED
                req.finish_time = time.monotonic()
                self._rejected.append(req)
                continue
            if self.allocator is not None:
                if covered == plen:
                    # Full block-aligned hit: every prompt token is
                    # cached, but sampling the first new token needs the
                    # logits at position plen-1 — recompute just that
                    # token into a COW copy of the last shared block
                    # (its other positions arrive by device-side copy).
                    shared = covered_ids[:-1]
                    start = plen - 1
                else:
                    shared = covered_ids
                    start = covered
                need = self.allocator.blocks_for(plen) - len(shared)
                # Pin EVERY matched block before alloc: alloc reclaims
                # refcount-0 cached blocks under pressure, and the match
                # — including the COW source, which is not part of the
                # request's own table — must survive that reclaim.  The
                # COW source's pin is dropped by the engine once the
                # device-side copy is enqueued (release_cow_sources);
                # device program order keeps the copy ahead of any later
                # owner's reset.
                self.allocator.acquire(covered_ids)
                fresh = (self.allocator.alloc(need)
                         if self._leaves_headroom(need) else None)
                if fresh is None:
                    self.allocator.free(covered_ids)
                    if not any(r is not None for r in self.slots):
                        # Nothing is running, so nothing will ever decref
                        # more blocks: even a fully drained pool cannot
                        # hold this request's committed prefix.  Terminal
                        # reject instead of spinning forever.
                        self.queue.popleft()
                        req.state = RequestState.REJECTED
                        req.finish_time = time.monotonic()
                        self._rejected.append(req)
                        continue
                    break           # pool dry: keep queued, stop here
                req.block_ids = shared + fresh
                req.fresh_block_ids = list(fresh)
                req.prefill_start = start
                if covered == plen:
                    req.cow_pairs = [(covered_ids[-1], fresh[0])]
                    req.chain_hash = (
                        self.allocator._meta[covered_ids[-1]][0])
                else:
                    req.cow_pairs = []
                    req.chain_hash = last_hash
                req.hashed_blocks = len(shared)
                req.prefix_tokens_total += plen
                req.prefix_hit_tokens_total += start
                self.prefix_hit_blocks_total += len(shared)
                self.cow_copies_total += len(req.cow_pairs)
                self.prefix_tokens_total += plen
                self.prefix_hit_tokens_total += start
            self.queue.popleft()
            i = free.popleft()
            req.slot = i
            req.state = RequestState.RUNNING
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            if req.admit_time is None:       # readmits keep the first wait
                req.admit_time = time.monotonic()
            self.slots[i] = req
            admitted.append(req)
        return admitted

    def register_prefix(self, req: Request) -> None:
        """Publish ``req``'s newly committed full blocks in the hash
        index (engine hook, called after prefill dispatch and after each
        round's commit).  Registration trails the committed boundary
        strictly — a registered block is full and below ``cache_len``,
        so in-flight speculative writes (always at or above the
        committed boundary) can never touch a shared block, which is
        what makes sharing safe under the pipelined schedule."""
        if not self._caching() or req.slot is None:
            return
        bs = self.allocator.block_size
        toks = req.prompt + req.output
        full = min(req.cache_len, len(toks)) // bs
        full = min(full, len(req.block_ids))
        while req.hashed_blocks < full:
            i = req.hashed_blocks
            chunk = tuple(int(t) for t in toks[i * bs:(i + 1) * bs])
            req.chain_hash = self.allocator.register(
                req.block_ids[i], req.chain_hash, chunk)
            req.hashed_blocks += 1

    def release_cow_sources(self, req: Request) -> None:
        """Drop the admission-time pins on ``req``'s copy-on-write source
        blocks (engine hook, called once the device-side block copy has
        been ENQUEUED — program order then keeps the copy ahead of any
        later owner's writes even if the source is reclaimed now)."""
        if self._caching() and req.cow_pairs:
            self.allocator.free([src for src, _ in req.cow_pairs])

    def pop_rejected(self) -> List[Request]:
        out, self._rejected = self._rejected, []
        return out

    def drop_from_queue(self, req: Request) -> None:
        """Remove a queued request that reached a terminal state while
        waiting.  Pipelined reconciliation needs this: a request can be
        preempted at plan time and then FINISH when the round it was
        still part of is collected one iteration later — it must not be
        readmitted and recomputed."""
        try:
            self.queue.remove(req)
        except ValueError:
            pass

    # ---------------------------------------------------------- block budget
    def ensure_capacity(self, req: Request, n_tokens: int
                        ) -> Tuple[List[int], List[Request]]:
        """Grow ``req``'s allocation to cover ``n_tokens`` KV slots,
        preempting the youngest other running requests while the pool is
        dry.  Returns (newly allocated block ids, preempted requests).
        The caller must reset ``kv_pos`` of the new blocks and mirror the
        table rows to the device caches."""
        assert self.allocator is not None
        need = self.allocator.blocks_for(n_tokens) - len(req.block_ids)
        if need <= 0:
            return [], []
        preempted: List[Request] = []
        while True:
            blocks = self.allocator.alloc(need)
            if blocks is not None:
                req.block_ids.extend(blocks)
                return blocks, preempted
            victim = self._pick_victim(exclude=req)
            if victim is None:
                # Pool dry with nothing else to preempt.  Under prefix
                # caching this is reachable (optimistic admission lets a
                # request in on its uncovered suffix): self-preempt the
                # requester.  Its committed full blocks stay registered
                # and warm, so readmission resumes through the cache —
                # the readmit prefill recomputes at most one partial
                # block and emits a token, so progress is monotone.
                assert self.prefix_cache, (
                    "pool exhausted with nothing to preempt — the single-"
                    "sequence pool guarantee should make this unreachable")
                self.preempt(req)
                preempted.append(req)
                return [], preempted
            self.preempt(victim)
            preempted.append(victim)

    def _pick_victim(self, exclude: Request) -> Optional[Request]:
        running = [r for r in self.slots if r is not None and r is not exclude]
        if not running:
            return None
        return max(running, key=lambda r: r.admit_seq)   # LIFO: youngest

    def preempt(self, req: Request) -> None:
        """Evict-and-requeue: decref every block, requeue at the *front*
        so the request readmits first and recomputes its prefix
        (prompt + emitted output) on readmission.  Under prefix caching
        the decref leaves registered blocks warm in the hash index, so
        the recompute usually collapses to a tail prefill over at most
        one partial block.

        The ``appendleft`` is also the starvation guard: every readmit
        sits ahead of every fresh arrival (``submit`` appends), FIFO in
        admission order within a preemption wave — see
        :meth:`assert_readmit_fifo`."""
        assert self.allocator is not None and req.slot is not None
        self.allocator.free(req.block_ids)
        req.block_ids = []
        self.slots[req.slot] = None
        req.slot = None
        req.cache_len = 0
        req.prefill_start = 0
        req.fresh_block_ids = []
        req.cow_pairs = []
        req.hashed_blocks = 0
        req.chain_hash = None
        req.state = RequestState.QUEUED
        req.preemptions += 1
        self.preempted_total += 1
        self.queue.appendleft(req)

    def shrink_to(self, req: Request, n_tokens: int) -> List[int]:
        """Return the speculative-tail blocks beyond ``n_tokens`` committed
        slots to the pool (post-round rollback is free)."""
        assert self.allocator is not None
        keep = self.allocator.blocks_for(n_tokens)
        freed = req.block_ids[keep:]
        if freed:
            del req.block_ids[keep:]
            self.allocator.free(freed)
        return freed

    def release(self, req: Request) -> None:
        if req.slot is not None:
            self.slots[req.slot] = None
            req.slot = None
        if self.allocator is not None and req.block_ids:
            self.allocator.free(req.block_ids)
            req.block_ids = []

    # ------------------------------------------------------------- telemetry
    @property
    def active_mask(self) -> np.ndarray:
        return np.array([r is not None for r in self.slots], bool)

    @property
    def running(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    def kv_blocks_in_use(self) -> int:
        """Blocks charged against the pool (paged), or the dense-row
        equivalent (active slots x blocks-per-row) so the same telemetry
        field plots memory-vs-throughput across both layouts."""
        if self.allocator is not None:
            return self.allocator.n_used
        return int(self.active_mask.sum()) * self.serving.blocks_per_seq()

    def kv_blocks_total(self) -> int:
        if self.allocator is not None:
            return self.allocator.num_blocks
        return self.serving.max_batch_size * self.serving.blocks_per_seq()

    def kv_block_bytes(self) -> int:
        """Bytes one pool block costs (0 when never sized — dense
        engines or direct-driver schedulers)."""
        return self.block_bytes

    def kv_bytes_total(self) -> int:
        """Pool footprint in bytes under the serving storage mode."""
        return self.kv_blocks_total() * self.block_bytes

    def kv_bytes_in_use(self) -> int:
        return self.kv_blocks_in_use() * self.block_bytes

    def kv_blocks_cached(self) -> int:
        """Warm unreferenced blocks parked on the evictable LRU."""
        if self.allocator is not None:
            return self.allocator.n_cached
        return 0

    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slots)
