"""Paged KV-cache data plane: allocator, block-table write/gather, the
paged Pallas kernel, and the headline guarantee — the paged engine emits
byte-identical token streams to the dense engine for every registered
policy (same seed, same requests), including under pool pressure with
preemption + recompute-on-readmit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import ServingConfig, SpecDecodeConfig
from repro.core.policies import available_policies
from repro.kernels import ref
from repro.kernels.ragged_attention import (live_blocks,
                                            paged_ragged_verify_attention)
from repro.models import cache as cache_lib
from repro.models.module import init_params
from repro.models.transformer import model_specs
from repro.serving.engine import ServingEngine
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import BlockAllocator, LookaheadScheduler

from paged_inputs import paged_attn_inputs

jax.config.update("jax_platform_name", "cpu")

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# BlockAllocator
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_roundtrip():
    a = BlockAllocator(num_blocks=8, block_size=16)
    b1 = a.alloc(3)
    b2 = a.alloc(4)
    assert len(b1) == 3 and len(b2) == 4
    assert len(set(b1) | set(b2)) == 7          # disjoint
    assert a.n_free == 1 and a.n_used == 7
    assert a.alloc(2) is None                    # short: no state change
    assert a.n_free == 1
    a.free(b1)
    assert a.n_free == 4
    b3 = a.alloc(4)
    assert b3 is not None and a.n_free == 0
    assert a.alloc(0) == []


def test_allocator_blocks_for():
    a = BlockAllocator(num_blocks=4, block_size=16)
    assert a.blocks_for(0) == 0
    assert a.blocks_for(1) == 1
    assert a.blocks_for(16) == 1
    assert a.blocks_for(17) == 2
    assert a.blocks_for(48) == 3


# ---------------------------------------------------------------------------
# Scheduler: block-budget admission, grow, preempt, readmit
# ---------------------------------------------------------------------------

def _paged_sched(slots=2, max_seq=64, bs=8, nblocks=None):
    sv = ServingConfig(max_batch_size=slots, max_seq_len=max_seq,
                       paged_kv=True, kv_block_size=bs,
                       num_kv_blocks=nblocks)
    return LookaheadScheduler(sv, SpecDecodeConfig())


def test_paged_admission_charges_prefill_blocks():
    # 7 blocks charged, 2 left as r1's growth headroom
    s = _paged_sched(slots=2, max_seq=64, bs=8, nblocks=9)
    r1 = Request(0, prompt=[1] * 20, max_new_tokens=8)   # 3 blocks
    r2 = Request(1, prompt=[1] * 30, max_new_tokens=8)   # 4 blocks
    s.submit(r1), s.submit(r2)
    assert len(s.admit()) == 2
    assert s.allocator.n_used == 7
    assert len(r1.block_ids) == 3 and len(r2.block_ids) == 4


def test_paged_admission_queues_when_pool_dry():
    s = _paged_sched(slots=2, max_seq=64, bs=8, nblocks=8)
    r1 = Request(0, prompt=[1] * 40, max_new_tokens=8)   # 5 blocks
    r2 = Request(1, prompt=[1] * 40, max_new_tokens=8)   # 5 blocks > 3 free
    s.submit(r1), s.submit(r2)
    admitted = s.admit()
    assert admitted == [r1]
    assert r2.state == RequestState.QUEUED      # queued, NOT rejected
    s.release(r1)
    assert s.admit() == [r2]                    # pool freed -> admits


def test_grow_preempts_youngest_and_readmits():
    s = _paged_sched(slots=2, max_seq=64, bs=8, nblocks=9)
    old = Request(0, prompt=[1] * 24, max_new_tokens=20)  # 3 blocks
    young = Request(1, prompt=[1] * 24, max_new_tokens=20)
    s.submit(old), s.submit(young)
    assert len(s.admit()) == 2
    assert s.allocator.n_free == 3
    # old wants 5 more blocks: must evict young
    new, preempted = s.ensure_capacity(old, 64)
    assert preempted == [young]
    assert young.state == RequestState.QUEUED and young.slot is None
    assert young.block_ids == [] and young.preemptions == 1
    assert len(old.block_ids) == 8
    assert s.queue[0] is young                   # front of queue: readmits first
    # shrink old back; young readmits into the freed budget
    s.shrink_to(old, 24)
    assert len(old.block_ids) == 3
    young.output = [5, 7]                        # emitted before preemption
    assert young.prefill_tokens() == [1] * 24 + [5]
    assert s.admit() == [young]
    assert len(young.block_ids) == 4             # 25-token recompute prefix


def test_paged_admission_leaves_growth_headroom():
    """Admission keeps two free blocks for every running sequence, so the
    next rounds' growth does not have to preempt what was just admitted."""
    s = _paged_sched(slots=3, max_seq=64, bs=8, nblocks=12)
    r1, r2, r3 = (Request(i, prompt=[1] * 24, max_new_tokens=8)  # 3 blocks
                  for i in range(3))
    for r in (r1, r2, r3):
        s.submit(r)
    assert s.admit() == [r1, r2]                 # r3 would leave 3 < 2 * 2
    assert r3.state == RequestState.QUEUED and s.allocator.n_free == 6
    s.release(r2)
    assert s.admit() == [r3]                     # 9 - 3 >= 2 * 1


def test_oversize_is_rejected_not_silently_dropped():
    s = _paged_sched(slots=1, max_seq=32)
    big = Request(0, prompt=[0] * 30, max_new_tokens=30)
    ok = Request(1, prompt=[0] * 4, max_new_tokens=4)
    s.submit(big), s.submit(ok)
    assert s.admit() == [ok]                     # big skipped, ok admitted
    assert big.state == RequestState.REJECTED
    assert big.finish_time is not None and big.done
    assert s.pop_rejected() == [big]
    assert s.pop_rejected() == []                # drained


# ---------------------------------------------------------------------------
# Cache primitives: paged write/gather == dense layout
# ---------------------------------------------------------------------------

def test_paged_write_gather_matches_dense_layout():
    rng = np.random.RandomState(3)
    b, t, kv, d, bs, maxb, n = 2, 5, 2, 8, 4, 4, 10
    w = maxb * bs
    positions = jnp.asarray(rng.randint(0, w - t, size=(b, 1))
                            + np.arange(t)[None])
    k_new = jnp.asarray(rng.randn(b, t, kv, d), jnp.float32)
    v_new = jnp.asarray(rng.randn(b, t, kv, d), jnp.float32)
    # dense ring at full width: slot = pos (identity)
    dk = jnp.zeros((b, w, kv, d)); dv = jnp.zeros((b, w, kv, d))
    dk, dv = cache_lib.write_kv(dk, dv, k_new, v_new, positions)
    dpos = cache_lib.write_pos(jnp.full((b, w), -1, jnp.int32), positions)
    # paged pool with disjoint scrambled tables
    perm = rng.permutation(n)
    table = jnp.asarray(np.stack([perm[:maxb], perm[maxb:2 * maxb]]))
    pk = jnp.zeros((n, bs, kv, d)); pv = jnp.zeros((n, bs, kv, d))
    ppos = jnp.full((n, bs), -1, jnp.int32)
    pk, pv = cache_lib.write_kv_paged(pk, pv, k_new, v_new, positions, table)
    ppos = cache_lib.write_pos_paged(ppos, positions, table)
    gk, gv = cache_lib.gather_paged_kv(pk, pv, table)
    gpos = cache_lib.gather_paged_pos(ppos, table)
    np.testing.assert_array_equal(np.asarray(gk), np.asarray(dk))
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(dv))
    np.testing.assert_array_equal(np.asarray(gpos), np.asarray(dpos))


def test_paged_write_respects_keep_mask_and_unallocated():
    b, t, kv, d, bs, maxb, n = 1, 4, 1, 4, 4, 3, 4
    table = jnp.asarray([[2, -1, -1]])           # only block 2 allocated
    positions = jnp.asarray([[2, 3, 4, 5]])      # 4,5 land in unalloc block 1
    keep = jnp.asarray([[True, False, True, True]])
    k_new = jnp.ones((b, t, kv, d)); v_new = jnp.ones((b, t, kv, d))
    pk = jnp.zeros((n, bs, kv, d)); pv = jnp.zeros((n, bs, kv, d))
    ppos = jnp.full((n, bs), -1, jnp.int32)
    pk, _ = cache_lib.write_kv_paged(pk, pv, k_new, v_new, positions, table,
                                     keep=keep)
    ppos = cache_lib.write_pos_paged(ppos, positions, table, keep=keep)
    # only position 2 (block 2, offset 2) survives: pos 3 is keep-masked,
    # 4/5 hit the unallocated block and are dropped
    got = np.asarray(ppos)
    assert got[2, 2] == 2
    assert (got.flatten() == -1).sum() == n * bs - 1
    assert np.asarray(pk)[2, 2].sum() == kv * d
    assert np.asarray(pk).sum() == kv * d


def test_reset_blocks_marks_empty():
    ppos = jnp.zeros((4, 2), jnp.int32)
    out = cache_lib.reset_blocks(ppos, [1, 3])
    np.testing.assert_array_equal(np.asarray(out),
                                  [[0, 0], [-1, -1], [0, 0], [-1, -1]])


def test_paged_cache_struct_shapes_and_guard():
    cfg = get_config("smollm-135m").reduced()
    c = cache_lib.paged_cache_struct(cfg, batch=3, max_len=64, num_blocks=8,
                                     block_size=16, dtype=jnp.float32)
    assert c["k"].shape == (cfg.num_layers, 8, 16,
                            cache_lib.eff_kv_heads(cfg),
                            cfg.resolved_head_dim)
    assert c["block_table"].shape == (3, 4)
    assert c["kv_pos"].shape == (8, 16)
    assert cache_lib.is_paged(c)
    with pytest.raises(AssertionError):          # pool < one max-len seq
        cache_lib.paged_cache_struct(cfg, 1, 256, num_blocks=2,
                                     block_size=16)
    ssm = get_config("mamba2-130m").reduced()
    assert not cache_lib.supports_paged(ssm)
    with pytest.raises(ValueError):
        cache_lib.paged_cache_struct(ssm, 1, 64, 8, 16)


# ---------------------------------------------------------------------------
# Paged Pallas kernel vs oracle (interpret mode)
# ---------------------------------------------------------------------------

PAGED_SHAPES = [
    # b, t, h, kv, d, n_blocks, bs, maxb, window[, layout]
    (2, 1, 8, 2, 64, 12, 16, 4, None),      # plain decode, GQA 4x
    (3, 6, 8, 8, 64, 20, 16, 5, None),      # verify, MHA
    (2, 11, 12, 4, 128, 9, 8, 6, None),     # verify, SL_max+1 queries
    (2, 4, 4, 2, 32, 10, 16, 4, 24),        # sliding window
    (3, 5, 8, 2, 64, 40, 16, 13, None),     # MAXB not a multiple of P = 8
    (3, 2, 8, 4, 64, 120, 16, 40, None, "spare"),   # extent << MAXB
    (2, 3, 8, 2, 64, 30, 16, 10, None, "overflow"),  # q_pos past MAXB*BS
    (2, 3, 4, 2, 32, 60, 16, 24, 40, "full"),  # window skips whole chunks
    (2, 5, 9, 3, 64, 260, 16, 128, None, "full"),  # 16 chunks, smollm heads
]


@pytest.mark.parametrize("shape", PAGED_SHAPES)
def test_paged_kernel_vs_oracle(shape):
    b, t, h, kv, d, n, bs, maxb, window, layout = (*shape, "ragged")[:10]
    q, pk, pv, table, qpos, kvp = paged_attn_inputs(
        b, t, h, kv, d, n, bs, maxb, seed=b * 10 + t, layout=layout)
    out = paged_ragged_verify_attention(q, pk, pv, table, qpos, kvp,
                                        window=window, interpret=True)
    want = ref.paged_ragged_verify_attention_ref(q, pk, pv, table, qpos,
                                                 kvp, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_paged_kernel_never_reads_past_live_extent():
    """Every pool block that a row's table holds only past the row's last
    query position is NaN: the kernel neither copies nor computes on it,
    so its output is finite and equals the oracle's on a finite pool."""
    b, t, h, kv, d, n, bs, maxb = 3, 3, 8, 2, 64, 120, 16, 40
    q, pk, pv, table, qpos, kvp = paged_attn_inputs(
        b, t, h, kv, d, n, bs, maxb, seed=3, layout="spare")
    tab = np.asarray(table)
    hi = live_blocks(np.asarray(qpos).max(axis=1), bs, maxb)
    live = {int(e) for i in range(b) for e in tab[i, :hi[i]] if e >= 0}
    dead = sorted({int(e) for e in tab[tab >= 0]} - live)
    assert len(dead) > b * 20
    poisoned = [p.at[jnp.asarray(dead)].set(jnp.nan) for p in (pk, pv)]
    out = paged_ragged_verify_attention(q, *poisoned, table, qpos, kvp,
                                        interpret=True)
    want = ref.paged_ragged_verify_attention_ref(q, pk, pv, table, qpos, kvp)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_paged_ref_matches_dense_ref_on_identity_table():
    """An identity block table makes the paged oracle degenerate to the
    dense ring oracle — the layout-independence anchor."""
    b, t, h, kv, d, bs, maxb = 2, 3, 4, 2, 32, 8, 4
    w = bs * maxb
    q, kb, vb, q_pos, kv_pos = None, None, None, None, None
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    kb = jax.random.normal(ks[1], (b, w, kv, d))
    vb = jax.random.normal(ks[2], (b, w, kv, d))
    lens = jnp.asarray([10, 25])
    q_pos = lens[:, None] + jnp.arange(t)[None]
    kv_pos = jnp.where(jnp.arange(w)[None] < (lens[:, None] + t),
                       jnp.arange(w)[None], -1)
    want = ref.ragged_verify_attention_ref(q, kb, vb, q_pos, kv_pos)
    # batch-strided pool: seq i owns blocks [i*maxb, (i+1)*maxb)
    pool_k = kb.reshape(b * maxb, bs, kv, d)
    pool_v = vb.reshape(b * maxb, bs, kv, d)
    ppos = kv_pos.reshape(b * maxb, bs)
    table = jnp.arange(b * maxb, dtype=jnp.int32).reshape(b, maxb)
    got = ref.paged_ragged_verify_attention_ref(q, pool_k, pool_v, table,
                                                q_pos, ppos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Engine: paged == dense, byte for byte
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_pair():
    cfg = get_config("smollm-135m").reduced()
    pt = init_params(model_specs(cfg), jax.random.PRNGKey(1), jnp.float32)
    noise = init_params(model_specs(cfg), jax.random.PRNGKey(7), jnp.float32)
    pd = jax.tree_util.tree_map(lambda a, b: a + 0.05 * b, pt, noise)
    return cfg, pt, pd


def _run_engine(cfg, pt, pd, policy, *, paged, prompts, max_new=16,
                temperature=0.0, nblocks=None, bs=16, batch=2,
                max_seq=128, seed=0):
    spec = SpecDecodeConfig(policy=policy, temperature=temperature)
    sv = ServingConfig(max_batch_size=batch, max_seq_len=max_seq,
                       paged_kv=paged, kv_block_size=bs,
                       num_kv_blocks=nblocks)
    eng = ServingEngine(pt, cfg, pd, cfg, spec, sv, seed=seed)
    reqs = [Request(i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    metrics = eng.run(reqs)
    return [r.output for r in reqs], metrics, eng


@pytest.mark.parametrize("policy", available_policies())
def test_paged_engine_exactness_all_policies(small_pair, policy):
    """The tentpole guarantee: byte-identical token streams from the
    dense and paged engines for every registered policy at a fixed seed
    (the block pool is a *layout*, never a semantics, change)."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in (7, 12, 5)]
    dense, _, _ = _run_engine(cfg, pt, pd, policy, paged=False,
                              prompts=prompts)
    paged, mp, _ = _run_engine(cfg, pt, pd, policy, paged=True,
                               prompts=prompts)
    assert dense == paged, policy
    assert mp["kv_blocks_peak"] <= mp["kv_pool_blocks"]


def test_paged_engine_exactness_hybrid_family():
    """Hybrid exercises every bespoke paged path at once: n_attn-sliced
    pools, dense per-slot recurrent state riding alongside, the engine's
    recurrent-row scatter at prefill, and commit's masked re-advance over
    a paged cache."""
    cfg = get_config("recurrentgemma-2b").reduced()
    assert cfg.family == "hybrid"
    pt = init_params(model_specs(cfg), jax.random.PRNGKey(1), jnp.float32)
    noise = init_params(model_specs(cfg), jax.random.PRNGKey(9), jnp.float32)
    pd = jax.tree_util.tree_map(lambda a, b: a + 0.05 * b, pt, noise)
    prompts = [list(range(3, 11)), list(range(5, 12))]
    dense, _, _ = _run_engine(cfg, pt, pd, "dsde", paged=False,
                              prompts=prompts, max_new=12)
    paged, _, _ = _run_engine(cfg, pt, pd, "dsde", paged=True,
                              prompts=prompts, max_new=12)
    assert dense == paged


def test_paged_engine_exact_under_preemption(small_pair):
    """Pool pressure forces evict-and-requeue; recompute-on-readmit must
    reproduce the dense outputs token for token (greedy)."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in (30, 25, 20)]
    dense, _, _ = _run_engine(cfg, pt, pd, "dsde", paged=False,
                              prompts=prompts, max_new=40, bs=8)
    # 16 blocks x 8 = 128 pool tokens shared by two live sequences whose
    # worst case is 30 + 40 + 11 = 81 each -> preemption must trigger
    paged, m, _ = _run_engine(cfg, pt, pd, "dsde", paged=True,
                              prompts=prompts, max_new=40, bs=8, nblocks=16)
    assert m["preemptions"] >= 1
    assert m["requests_finished"] == 3
    assert dense == paged


def test_paged_round_log_telemetry(small_pair):
    cfg, pt, pd = small_pair
    prompts = [list(range(1, 9))]
    _, m, eng = _run_engine(cfg, pt, pd, "dsde", paged=True, prompts=prompts)
    assert eng.round_log
    for rec in eng.round_log:
        assert rec["kv_blocks_in_use"] >= 0
        assert 0.0 <= rec["kv_pool_utilization"] <= 1.0
        assert rec["wall_s"] > 0.0
    assert m["kv_blocks_peak"] >= 1


def test_round_log_counts_verify_blocks_swept(small_pair):
    """The round log's ``verify_blocks_swept`` is the verify sweep's
    extent as the round program sees it: ``live_blocks`` of each carried
    row's last query position, the device's committed length + K."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(3)
    spec = SpecDecodeConfig(policy="dsde", temperature=0.0)
    sv = ServingConfig(max_batch_size=3, max_seq_len=128, paged_kv=True,
                       kv_block_size=4)
    eng = ServingEngine(pt, cfg, pd, cfg, spec, sv, seed=0)
    for i in range(5):
        eng.submit(Request(i, prompt=rng.randint(
            0, cfg.vocab_size, size=rng.randint(5, 25)).tolist(),
            max_new_tokens=int(rng.randint(8, 24))))
    seen = []
    round_fn = eng._round_fn

    def spy(k):
        fn = round_fn(k)

        def call(state, active):
            length = np.asarray(state.target_cache["length"])
            slots = [r.slot for r in eng.scheduler.running]
            seen.append(int(live_blocks(length[slots] + k, 4,
                                        sv.blocks_per_seq()).sum()))
            return fn(state, active)
        return call

    eng._round_fn = spy
    while eng.scheduler.has_work():
        eng.step()
    assert [r["verify_blocks_swept"] for r in eng.round_log] == seen
    assert max(seen) > 3 and len(set(seen)) > 3


def test_device_tables_mirror_allocator_every_round(small_pair):
    """Regression: post-round shrink must drop freed entries from the
    *device* block-table row immediately — a stale entry would gather a
    reallocated block's new owner's KV into this sequence's attention.
    Invariant: after every step, each running request's device row is
    exactly its host block_ids, and no block has two owners."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(7)
    spec = SpecDecodeConfig(policy="dsde", temperature=0.0)
    sv = ServingConfig(max_batch_size=3, max_seq_len=128, paged_kv=True,
                       kv_block_size=4)
    eng = ServingEngine(pt, cfg, pd, cfg, spec, sv, seed=0)
    for i in range(6):
        eng.submit(Request(i, prompt=rng.randint(
            0, cfg.vocab_size, size=rng.randint(5, 25)).tolist(),
            max_new_tokens=int(rng.randint(8, 24))))
    freed_events = []
    orig_shrink = eng.scheduler.shrink_to

    def shrink_spy(req, n_tokens):
        freed = orig_shrink(req, n_tokens)
        if freed:
            freed_events.append(len(freed))
        return freed

    eng.scheduler.shrink_to = shrink_spy
    while eng.scheduler.has_work():
        eng.step()
        bt = np.asarray(eng.state.target_cache["block_table"])
        owned = []
        for req in eng.scheduler.running:
            row = bt[req.slot]
            dev_ids = row[row >= 0].tolist()
            assert dev_ids == req.block_ids, (req.request_id, dev_ids,
                                              req.block_ids)
            owned += req.block_ids
        assert len(owned) == len(set(owned))     # single ownership
    assert freed_events                           # the scenario occurred


@pytest.mark.parametrize("caching", [False, True],
                         ids=["preempt", "prefix_cache"])
def test_pool_positions_stay_in_their_logical_block(small_pair, caching):
    """The paged kernel skips a table column once its first position
    lies past the row's last query: it relies on every slot of logical
    block ``j`` holding a position in ``[j*BS, (j+1)*BS)`` or -1.  After
    every pump of the pipelined engine, for every running row, through
    growth, post-round shrink and regrow, preemption and readmission, and
    with prefix-cache sharing and copy-on-write forks."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(5)
    bs = 8
    shared = rng.randint(0, cfg.vocab_size, size=24).tolist()
    prompts = [(shared if caching else [])
               + rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in ((6, 3, 1, 0) if caching else (30, 25, 20, 12))]
    spec = SpecDecodeConfig(policy="dsde", temperature=0.0)
    sv = ServingConfig(max_batch_size=2, max_seq_len=128, paged_kv=True,
                       kv_block_size=bs, num_kv_blocks=16, pipelined=True,
                       prefix_caching=caching)
    eng = ServingEngine(pt, cfg, pd, cfg, spec, sv, seed=0)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, prompt=p, max_new_tokens=40))
    checked = 0
    while eng.has_pending_work():
        eng.pump()
        tc = eng.state.target_cache
        table = np.asarray(tc["block_table"])
        kv_pos = np.asarray(tc["kv_pos"])
        for req in eng.scheduler.running:
            for j, blk in enumerate(table[req.slot]):
                if blk < 0:
                    continue
                pos = kv_pos[blk]
                ok = (pos == -1) | ((pos >= j * bs) & (pos < (j + 1) * bs))
                assert ok.all(), (req.request_id, j, blk, pos)
                checked += 1
    eng.drain()
    assert checked and eng.scheduler.preempted_total >= 1
    if caching:
        assert eng.scheduler.prefix_hit_blocks_total > 0


def test_admission_refreshes_scheduler_sl_mirror(small_pair):
    """Regression: block planning for a fresh request's first round must
    use its initial SL, not the slot's previous occupant's last
    prediction (a stale low SL under-allocates and drops accepted KV)."""
    cfg, pt, pd = small_pair
    spec = SpecDecodeConfig(policy="dsde", temperature=0.0)
    sv = ServingConfig(max_batch_size=2, max_seq_len=128, paged_kv=True)
    eng = ServingEngine(pt, cfg, pd, cfg, spec, sv, seed=0)
    eng.scheduler.sl_pred[:] = 1                  # stale previous-occupant SL
    eng.submit(Request(0, prompt=list(range(1, 9)), max_new_tokens=8))
    eng._admit()
    slot = eng.scheduler.running[0].slot
    assert eng.scheduler.sl_pred[slot] == eng.policy.initial_sl_value()


def test_rejected_requests_surface_in_summary(small_pair):
    cfg, pt, pd = small_pair
    big = [0] * 120                               # 120 + 16 + 11 > 128
    ok = list(range(1, 7))
    for paged in (False, True):
        _, m, _ = _run_engine(cfg, pt, pd, "dsde", paged=paged,
                              prompts=[big, ok], max_new=16)
        assert m["requests_rejected"] == 1
        assert m["requests_finished"] == 1


def test_paged_rejects_unsupported_family():
    cfg = get_config("mamba2-130m").reduced()
    pt = init_params(model_specs(cfg), jax.random.PRNGKey(1), jnp.float32)
    with pytest.raises(ValueError):
        ServingEngine(pt, cfg, pt, cfg, SpecDecodeConfig(),
                      ServingConfig(max_batch_size=1, max_seq_len=64,
                                    paged_kv=True))
