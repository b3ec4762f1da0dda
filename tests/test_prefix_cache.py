"""Prefix caching (DESIGN.md §12): refcounted copy-on-write block
sharing in the paged KV pool.

The headline guarantee mirrors test_paging's: prefix caching is a
*layout/work* optimization, never a semantics change — greedy token
streams from a cache-warm engine are byte-identical to the dense
(cache-free by construction) engine for every registered policy ×
drafter × schedule, including under forced preemption and forced
eviction.  Plus: allocator unit tests (refcount / hash index / LRU
eviction / COW fork), the coverage-aware admission boundary, and a
property test over random allocator traces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.config import ServingConfig, SpecDecodeConfig
from repro.core.drafters import available_drafters
from repro.core.policies import available_policies
from repro.models import cache as cache_lib
from repro.models.module import init_params
from repro.models.transformer import model_specs
from repro.serving.engine import ServingEngine
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import BlockAllocator, LookaheadScheduler

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# BlockAllocator: refcounts, hash index, LRU eviction, COW fork
# ---------------------------------------------------------------------------

def _register_chain(a, blocks, tokens):
    """Register ``blocks`` as the chain holding ``tokens`` (full blocks)."""
    h = None
    bs = a.block_size
    for i, b in enumerate(blocks):
        h = a.register(b, h, tuple(tokens[i * bs:(i + 1) * bs]))
    return h


def test_refcount_shared_blocks_survive_one_owner_freeing():
    a = BlockAllocator(num_blocks=8, block_size=4)
    blocks = a.alloc(2)
    _register_chain(a, blocks, list(range(8)))
    a.acquire(blocks)                      # second owner
    a.free(blocks)                         # first owner leaves
    assert all(a.refcount[b] == 1 for b in blocks)
    assert a.n_cached == 0                 # still referenced, not warm
    a.free(blocks)                         # last owner leaves
    assert a.n_cached == 2                 # registered -> warm, not free
    ids, h, covered = a.match_prefix(list(range(8)))
    assert ids == blocks and covered == 8  # still matchable
    a.acquire(ids)                         # revived from the warm list
    assert a.n_cached == 0 and all(a.refcount[b] == 1 for b in ids)


def test_unregistered_blocks_free_immediately():
    a = BlockAllocator(num_blocks=4, block_size=4)
    blocks = a.alloc(3)
    a.free(blocks)
    assert a.n_cached == 0 and a.n_free == 4


def test_match_prefix_walks_full_blocks_only():
    a = BlockAllocator(num_blocks=8, block_size=4)
    blocks = a.alloc(3)
    _register_chain(a, blocks[:2], list(range(8)))   # 2 full blocks cached
    a.free(blocks)
    ids, _, covered = a.match_prefix(list(range(11)))
    assert ids == blocks[:2] and covered == 8        # tail block never hashed
    ids, _, covered = a.match_prefix(list(range(6)))
    assert ids == blocks[:1] and covered == 4        # partial second block
    ids, _, covered = a.match_prefix([99] + list(range(1, 8)))
    assert ids == [] and covered == 0                # first-block mismatch


def test_match_verifies_stored_tokens_not_just_hashes():
    """A hash collision must degrade to a cache miss, never a false hit:
    the index match is confirmed against the stored token chunk."""
    a = BlockAllocator(num_blocks=4, block_size=2)
    blocks = a.alloc(1)
    h = a.register(blocks[0], None, (1, 2))
    a.free(blocks)
    # sabotage: alias a different chunk's hash onto the cached block
    a._index[BlockAllocator._chain_hash(None, (3, 4))] = blocks[0]
    ids, _, covered = a.match_prefix([3, 4])
    assert ids == [] and covered == 0
    assert a.match_prefix([1, 2])[0] == blocks       # true owner still hits


def test_lru_eviction_only_under_pressure_oldest_first():
    a = BlockAllocator(num_blocks=4, block_size=2)
    b1 = a.alloc(1); _register_chain(a, b1, [1, 2]); a.free(b1)
    b2 = a.alloc(1); _register_chain(a, b2, [3, 4]); a.free(b2)
    assert a.n_cached == 2 and a.evictions == 0
    got = a.alloc(2)                       # 2 truly-free remain: no eviction
    assert a.evictions == 0 and a.n_cached == 2
    got2 = a.alloc(1)                      # pressure: evict the LRU-oldest
    assert a.evictions == 1
    assert a.match_prefix([1, 2])[0] == []           # b1 gone
    assert a.match_prefix([3, 4])[0] == b2           # b2 survives
    assert a.alloc(2) is None              # 1 warm + 0 free < 2: unchanged
    a.free(got + got2)
    a.check_invariants()


def test_registration_is_first_writer_wins():
    a = BlockAllocator(num_blocks=4, block_size=2)
    b1 = a.alloc(1)
    b2 = a.alloc(1)
    h1 = a.register(b1[0], None, (5, 6))
    h2 = a.register(b2[0], None, (5, 6))   # duplicate content
    assert h1 == h2
    assert a.match_prefix([5, 6])[0] == b1           # index kept the first
    a.free(b1), a.free(b2)
    assert a.n_cached == 1                 # the losing copy freed for real
    a.check_invariants()


def test_fork_cow_allocates_then_releases_source():
    a = BlockAllocator(num_blocks=3, block_size=2)
    src = a.alloc(1)
    _register_chain(a, src, [7, 8])
    a.acquire(src)                         # a second sharer holds src
    dst = a.fork_cow(src[0])               # the sharer forks off a copy
    assert dst is not None and dst != src[0]
    assert a.refcount[src[0]] == 1 and a.refcount[dst] == 1
    a.free(src)                            # original owner leaves
    assert a.n_cached == 1                 # src stays warm + indexed
    assert a.match_prefix([7, 8])[0] == src
    a.check_invariants()


# ---------------------------------------------------------------------------
# Admission: coverage discount, COW plan, pin-before-alloc
# ---------------------------------------------------------------------------

def _cached_sched(slots=1, max_seq=128, bs=16, nblocks=None, max_la=3):
    sv = ServingConfig(max_batch_size=slots, max_seq_len=max_seq,
                       paged_kv=True, kv_block_size=bs,
                       num_kv_blocks=nblocks, prefix_caching=True)
    return LookaheadScheduler(sv, SpecDecodeConfig(policy="static",
                                                   static_sl=max_la - 1))


def _prime(s, prompt, emitted=0):
    """Admit + commit a request so its prompt blocks land in the index,
    then finish it (blocks drop to the warm list, still registered)."""
    req = Request(10_000 + s._admit_seq, prompt=list(prompt),
                  max_new_tokens=max(emitted, 1))
    s.submit(req)
    assert s.admit() == [req]
    req.cache_len = len(prompt) + emitted
    s.register_prefix(req)
    s.release(req)
    return req


def test_admission_fits_only_because_of_cache_coverage():
    """Satellite regression: the oversize check must charge only the
    UNCOVERED suffix.  pool = 7x16 = 112 < prompt + max_new + lookahead
    = 116 (8 blocks), so a cold pool rejects — but with 6 prompt blocks
    cached the residual ask is 2 blocks and the request must admit."""
    prompt = list(range(97))
    cold = _cached_sched(nblocks=7)
    r = Request(0, prompt=list(prompt), max_new_tokens=16)
    cold.submit(r)
    assert cold.admit() == []
    assert r.state == RequestState.REJECTED
    warm = _cached_sched(nblocks=7)
    _prime(warm, prompt)                   # registers 97//16 = 6 blocks
    r2 = Request(1, prompt=list(prompt), max_new_tokens=16)
    warm.submit(r2)
    assert warm.admit() == [r2]
    assert r2.prefill_start == 96 and len(r2.fresh_block_ids) == 1
    warm.allocator.check_invariants()


def test_full_aligned_hit_plans_exactly_one_cow_pair():
    prompt = list(range(32))               # exactly 2 blocks
    s = _cached_sched(nblocks=16)
    _prime(s, prompt)
    r = Request(1, prompt=list(prompt), max_new_tokens=8)
    s.submit(r)
    assert s.admit() == [r]
    # last shared block forks; its final position is recomputed
    assert r.prefill_start == 31
    assert len(r.cow_pairs) == 1
    src, dst = r.cow_pairs[0]
    assert dst in r.fresh_block_ids and src not in r.block_ids
    assert s.allocator.refcount[src] == 1  # pinned until the copy enqueues
    s.release_cow_sources(r)
    assert s.allocator.n_cached == 1       # src back on the warm list
    s.allocator.check_invariants()


def test_admission_pins_matched_blocks_before_allocating():
    """Regression: alloc() reclaims warm blocks under pressure — the
    blocks the admission just MATCHED must be pinned first or the
    allocator can evict part of its own hit."""
    s = _cached_sched(nblocks=8, max_seq=128)
    chain_a = list(range(64))              # 4 blocks, primed FIRST (LRU-oldest)
    chain_b = list(range(500, 532))        # 2 blocks
    _prime(s, chain_a)
    _prime(s, chain_b)
    # free: 2, warm: A(4) + B(2).  The request matches all of A and needs
    # 3 fresh blocks -> alloc must evict one warm block, and the
    # LRU-oldest warm blocks are exactly the matched A blocks: only the
    # admission-time pin diverts the eviction onto B.
    r = Request(1, prompt=chain_a + list(range(100, 133)), max_new_tokens=16)
    s.submit(r)
    assert s.admit() == [r]
    assert r.prefill_start == 64           # the hit survived allocation
    assert s.allocator.evictions == 1      # pressure landed on B instead
    assert s.allocator.match_prefix(chain_a)[2] == 64
    s.allocator.check_invariants()


def test_preempted_request_recovers_coverage_on_readmit():
    prompt = list(range(40))
    s = _cached_sched(slots=2, nblocks=16)
    _prime(s, prompt)
    r = Request(1, prompt=list(prompt), max_new_tokens=8)
    s.submit(r)
    assert s.admit() == [r]
    assert r.prefill_start == 32
    s.preempt(r)
    assert (r.prefill_start, r.cow_pairs, r.hashed_blocks) == (0, [], 0)
    assert s.admit() == [r]                # readmits with coverage again
    assert r.prefill_start == 32
    s.allocator.check_invariants()


# ---------------------------------------------------------------------------
# Property test: random admit/grow/shrink/preempt/evict traces
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 2 ** 31 - 1), min_size=4, max_size=80),
       st.integers(4, 12))
def test_allocator_trace_invariants(ops, num_blocks):
    """free + warm + (unique referenced) always partition the pool, no
    block is simultaneously free and referenced, and every warm block
    stays reachable from the hash index — across random interleavings of
    alloc, free, acquire, register, and COW forks."""
    a = BlockAllocator(num_blocks=num_blocks, block_size=2)
    owned = []                             # [(blocks, registered_upto)]
    token = 0
    for x in ops:
        op = x % 5
        if op == 0:                        # alloc (admit / grow)
            n = (x // 5) % (num_blocks + 1)
            got = a.alloc(n)
            if got is not None and n > 0:
                owned.append([got, 0])
        elif op == 1 and owned:            # free (finish / preempt / shrink)
            blocks, _ = owned.pop((x // 5) % len(owned))
            a.free(blocks)
        elif op == 2 and owned:            # register a prefix chunk
            ent = owned[(x // 5) % len(owned)]
            if ent[1] < len(ent[0]):
                b = ent[0][ent[1]]
                parent = a._meta[ent[0][ent[1] - 1]][2] if ent[1] else None
                a.register(b, parent, (token, token + 1))
                token += 2
                ent[1] += 1
        elif op == 3 and owned:            # acquire (cache-hit share)
            blocks = owned[(x // 5) % len(owned)][0]
            a.acquire(blocks)
            owned.append([list(blocks), 0])
        elif op == 4 and owned:            # COW fork of a shared block
            # fork_cow consumes the caller's reference on src: the
            # forker's table swaps src for the private dst, like the
            # engine's full-aligned-hit admission does
            ent = owned[(x // 5) % len(owned)]
            j = (x // 7) % len(ent[0])
            dst = a.fork_cow(ent[0][j])
            if dst is not None:
                ent[0][j] = dst
                ent[1] = min(ent[1], j)    # dst is private, unregistered
        a.check_invariants()
    for blocks, _ in owned:
        a.free(blocks)
    a.check_invariants()
    assert a.n_free == num_blocks          # nothing leaked


# ---------------------------------------------------------------------------
# Engine: warm == cold == dense, byte for byte
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_pair():
    cfg = get_config("smollm-135m").reduced()
    pt = init_params(model_specs(cfg), jax.random.PRNGKey(1), jnp.float32)
    noise = init_params(model_specs(cfg), jax.random.PRNGKey(7), jnp.float32)
    pd = jax.tree_util.tree_map(lambda a, b: a + 0.05 * b, pt, noise)
    return cfg, pt, pd


RNG = np.random.RandomState(11)
SHARED = RNG.randint(0, 1000, size=40).tolist()
# batch 1 seeds the cache; batch 2 hits it: a partial-hit continuation, a
# full block-aligned repeat (the COW path), and a cold outlier
BATCH1 = [SHARED + RNG.randint(0, 1000, size=6).tolist()]
BATCH2 = [SHARED + RNG.randint(0, 1000, size=5).tolist(),
          SHARED[:32],
          RNG.randint(0, 1000, size=9).tolist()]


def _run_batches(cfg, pt, pd, policy, drafter, *, paged, prefix_caching,
                 pipelined, max_new=10, nblocks=None, bs=16, batch=2,
                 max_seq=128, batches=(BATCH1, BATCH2), kv_quant="none"):
    spec = SpecDecodeConfig(policy=policy, temperature=0.0, drafter=drafter)
    sv = ServingConfig(max_batch_size=batch, max_seq_len=max_seq,
                       paged_kv=paged, kv_block_size=bs,
                       num_kv_blocks=nblocks, prefix_caching=prefix_caching,
                       pipelined=pipelined, kv_quant=kv_quant)
    model = drafter == "model"
    eng = ServingEngine(pt, cfg, pd if model else None,
                        cfg if model else None, spec, sv, seed=0)
    outs, reqs_all = [], []
    for j, batch_prompts in enumerate(batches):
        reqs = [Request(j * 100 + i, prompt=list(p), max_new_tokens=max_new)
                for i, p in enumerate(batch_prompts)]
        m = eng.run(reqs)
        outs += [r.output for r in reqs]
        reqs_all += reqs
    return outs, m, eng, reqs_all


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["sync", "pipelined"])
@pytest.mark.parametrize("drafter", available_drafters())
@pytest.mark.parametrize("policy", available_policies())
def test_warm_streams_match_dense_matrix(small_pair, policy, drafter,
                                         pipelined):
    """The exactness contract, full matrix: greedy streams from the
    cache-warm paged engine are byte-identical to the DENSE engine
    (cache-free by construction) for every policy x drafter x schedule,
    and the warm run really did share (hit blocks > 0)."""
    cfg, pt, pd = small_pair
    dense, _, _, _ = _run_batches(cfg, pt, pd, policy, drafter, paged=False,
                                  prefix_caching=False, pipelined=pipelined)
    warm, m, _, reqs = _run_batches(cfg, pt, pd, policy, drafter, paged=True,
                                    prefix_caching=True, pipelined=pipelined)
    assert dense == warm, (policy, drafter, pipelined)
    assert m["prefix_cache_hit_blocks"] > 0
    assert m["cow_copies"] >= 1            # BATCH2 includes the exact repeat
    assert 0.0 < m["prefix_cache_hit_rate"] <= 1.0
    # per-request attribution: the continuation hit, the outlier did not
    assert reqs[1].prefix_hit_rate() > 0.0
    assert reqs[3].prefix_hit_rate() == 0.0


def test_warm_exact_under_forced_preemption(small_pair):
    """Pool pressure + sharing: preemption fires, readmits recover their
    coverage from the cache, streams stay dense-identical."""
    cfg, pt, pd = small_pair
    pre = SHARED[:24]
    prompts = [pre + RNG.randint(0, 1000, size=n).tolist()
               for n in (6, 3, 1)]
    kw = dict(max_new=40, bs=8, batches=(prompts,))
    dense, _, _, _ = _run_batches(cfg, pt, pd, "dsde", "model", paged=False,
                                  prefix_caching=False, pipelined=False, **kw)
    for pipelined in (False, True):
        warm, m, _, _ = _run_batches(cfg, pt, pd, "dsde", "model",
                                     paged=True, prefix_caching=True,
                                     pipelined=pipelined, nblocks=16, **kw)
        assert m["preemptions"] >= 1, pipelined
        assert m["requests_finished"] == 3
        assert dense == warm, pipelined


def test_warm_exact_under_forced_eviction(small_pair):
    """Cache entries are reclaimed LRU-under-pressure; an evicted prefix
    degrades to a miss, never to corruption."""
    cfg, pt, pd = small_pair
    a = SHARED[:32]
    b = RNG.randint(0, 1000, size=97).tolist()       # 7 blocks: drains pool
    batches = ([list(a)], [list(b)], [list(a)])
    kw = dict(max_new=8, nblocks=8, batch=1, batches=batches)
    dense, _, _, _ = _run_batches(cfg, pt, pd, "dsde", "model", paged=False,
                                  prefix_caching=False, pipelined=False, **kw)
    warm, m, eng, _ = _run_batches(cfg, pt, pd, "dsde", "model", paged=True,
                                   prefix_caching=True, pipelined=False, **kw)
    assert m["prefix_cache_evictions"] >= 1
    assert dense == warm
    eng.scheduler.allocator.check_invariants()


def test_prefix_cache_round_log_and_summary(small_pair):
    cfg, pt, pd = small_pair
    _, m, eng, _ = _run_batches(cfg, pt, pd, "dsde", "model", paged=True,
                                prefix_caching=True, pipelined=False)
    for rec in eng.round_log:
        assert 0.0 <= rec["kv_pool_utilization"] <= 1.0
        assert 0.0 <= rec["prefix_cache_hit_rate"] <= 1.0
        assert rec["prefix_cache_hit_blocks"] >= 0.0
        assert rec["cow_copies"] >= 0.0
        assert rec["kv_blocks_cached"] >= 0.0
    # the per-round hit-block deltas sum to the lifetime total
    assert sum(r["prefix_cache_hit_blocks"]
               for r in eng.round_log) == m["prefix_cache_hit_blocks"]
    assert 0.0 < m["kv_pool_utilization_mean"] <= 1.0
    assert m["kv_pool_utilization_peak"] >= m["kv_pool_utilization_mean"]


def test_warm_admission_prefills_only_the_tail(small_pair):
    """The perf claim behind the whole feature: a cache-hit admission
    runs the TAIL entry point over a bucket sized by the uncovered
    suffix, not the full prompt."""
    from repro.core import prefill as prefill_lib
    cfg, pt, pd = small_pair
    calls = []
    orig = prefill_lib.prefill_paged_tail

    def spy(params, c, pk, pv, kp, rows, tokens, *a, **kw):
        calls.append(tokens.shape[1])
        return orig(params, c, pk, pv, kp, rows, tokens, *a, **kw)

    prefill_lib.prefill_paged_tail = spy
    try:
        _, m, _, _ = _run_batches(cfg, pt, pd, "dsde", "model", paged=True,
                                  prefix_caching=True, pipelined=False)
    finally:
        prefill_lib.prefill_paged_tail = orig
    assert calls                           # warm admissions took the tail path
    # SHARED covers 40 tokens (2 full blocks); every warm bucket is far
    # narrower than the 46+-token full prompts' 64-wide bucket
    assert max(calls) <= 16


def test_prefix_caching_requires_paged_and_attention_families(small_pair):
    cfg, pt, pd = small_pair
    spec = SpecDecodeConfig(policy="dsde", temperature=0.0)
    sv = ServingConfig(max_batch_size=2, max_seq_len=128, paged_kv=False,
                       prefix_caching=True)
    eng = ServingEngine(pt, cfg, pd, cfg, spec, sv, seed=0)
    assert not eng.prefix_caching          # dense plane: silently off
    hyb = get_config("recurrentgemma-2b").reduced()
    ph = init_params(model_specs(hyb), jax.random.PRNGKey(1), jnp.float32)
    sv = ServingConfig(max_batch_size=2, max_seq_len=128, paged_kv=True,
                       prefix_caching=True)
    eng = ServingEngine(ph, hyb, ph, hyb, spec, sv, seed=0)
    assert not eng.prefix_caching          # recurrent state: off
    # ...and the engine still serves correctly with the flag ignored
    r = Request(0, prompt=list(range(3, 11)), max_new_tokens=4)
    m = eng.run([r])
    assert m["requests_finished"] == 1
    assert m["prefix_cache_hit_blocks"] == 0.0


# ---------------------------------------------------------------------------
# Prefix caching x quantized pool (DESIGN.md §13)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("drafter", ["model", "ngram"])
def test_warm_streams_match_cold_in_quant_plane(small_pair, drafter):
    """The §12 exactness contract holds INSIDE the quantized plane: a
    cache-warm int8 engine emits streams byte-identical to the cache-cold
    int8 engine (the fp stream is NOT the reference — storage
    quantization legitimately shifts it).  BATCH2's block-aligned repeat
    forces a COW fork, so this also pins copy_scales: a fork that
    dropped or misrouted the per-slot scales would corrupt the dequant
    of the whole forked block and diverge loudly."""
    cfg, pt, pd = small_pair
    cold, _, _, _ = _run_batches(cfg, pt, pd, "static", drafter, paged=True,
                                 prefix_caching=False, pipelined=False,
                                 kv_quant="int8")
    warm, m, _, _ = _run_batches(cfg, pt, pd, "static", drafter, paged=True,
                                 prefix_caching=True, pipelined=False,
                                 kv_quant="int8")
    assert cold == warm, drafter
    assert m["prefix_cache_hit_blocks"] > 0
    assert m["cow_copies"] >= 1


def test_warm_revival_restores_scale_state(small_pair):
    """LRU eviction + revival in the quantized plane: an evicted-then-
    revived prefix must come back with its scale state intact (the warm
    block's int8 payload is meaningless without it), and an actually
    reclaimed block must degrade to a miss, never to corruption."""
    cfg, pt, pd = small_pair
    a = SHARED[:32]
    b = RNG.randint(0, 1000, size=97).tolist()       # 7 blocks: drains pool
    batches = ([list(a)], [list(b)], [list(a)])
    kw = dict(max_new=8, nblocks=8, batch=1, batches=batches,
              kv_quant="int8")
    cold, _, _, _ = _run_batches(cfg, pt, pd, "static", "model", paged=True,
                                 prefix_caching=False, pipelined=False, **kw)
    warm, m, eng, _ = _run_batches(cfg, pt, pd, "static", "model",
                                   paged=True, prefix_caching=True,
                                   pipelined=False, **kw)
    assert m["prefix_cache_evictions"] >= 1
    assert cold == warm
    eng.scheduler.allocator.check_invariants()


def test_quant_pool_scale_leaves_present_in_engine_cache(small_pair):
    cfg, pt, pd = small_pair
    _, _, eng, _ = _run_batches(cfg, pt, pd, "static", "model", paged=True,
                                prefix_caching=True, pipelined=False,
                                kv_quant="int8", batches=(BATCH1,))
    tc = eng.state.target_cache
    assert cache_lib.is_quantized(tc)
    assert tc["k"].dtype == jnp.int8
    assert tc["k_scale"].shape == tc["k"].shape[:-1]
    # the mirrored draft pool is quantized too (same block ids, same mode)
    dc = eng.state.draft_cache
    assert cache_lib.is_quantized(dc)
