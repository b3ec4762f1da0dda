"""Plan → dispatch → collect pipeline and device-side termination.

The headline guarantee: the pipelined schedule (round N+1 enqueued
before round N is reconciled, host one round behind) emits byte-
identical greedy token streams to the synchronous engine for EVERY
registered policy, on both KV layouts, including under forced
preemption.  Plus the termination edge cases that device-side ``done``
tracking must get right: EOS exactly on a round boundary, a token
budget exhausted mid-round (truncate, never over-emit), and a finished
slot re-admitted while its last round is still in the pipelined window.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import ServingConfig, SpecDecodeConfig
from repro.core.policies import available_policies
from repro.models.module import init_params
from repro.models.transformer import forward, model_specs
from repro.serving.engine import ServingEngine
from repro.serving.request import Request, RequestState

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def small_pair():
    cfg = get_config("smollm-135m").reduced()
    pt = init_params(model_specs(cfg), jax.random.PRNGKey(1), jnp.float32)
    noise = init_params(model_specs(cfg), jax.random.PRNGKey(7), jnp.float32)
    pd = jax.tree_util.tree_map(lambda a, b: a + 0.05 * b, pt, noise)
    return cfg, pt, pd


def greedy_rollout(params, cfg, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits, _, _ = forward(params, cfg,
                               jnp.asarray([toks], jnp.int32), mode="train")
        toks.append(int(jnp.argmax(logits[0, -1, :cfg.vocab_size])))
    return toks[len(prompt):]


def _run(cfg, pt, pd, policy, *, pipelined, prompts, paged=False,
         max_new=16, eos=None, batch=2, max_seq=128, bs=16, nblocks=None,
         seed=0):
    spec = SpecDecodeConfig(policy=policy, temperature=0.0)
    sv = ServingConfig(max_batch_size=batch, max_seq_len=max_seq,
                       paged_kv=paged, kv_block_size=bs,
                       num_kv_blocks=nblocks, pipelined=pipelined)
    eng = ServingEngine(pt, cfg, pd, cfg, spec, sv, seed=seed)
    reqs = [Request(i, prompt=p, max_new_tokens=max_new, eos_token_id=eos)
            for i, p in enumerate(prompts)]
    metrics = eng.run(reqs)
    return [r.output for r in reqs], metrics, reqs, eng


# ---------------------------------------------------------------------------
# Byte-identity: pipelined == sync for every policy, both layouts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("policy", available_policies())
def test_pipelined_matches_sync_every_policy(small_pair, policy, paged):
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in (7, 12, 5)]
    sync, ms, _, _ = _run(cfg, pt, pd, policy, pipelined=False,
                          prompts=prompts, paged=paged)
    pipe, mp, reqs, _ = _run(cfg, pt, pd, policy, pipelined=True,
                             prompts=prompts, paged=paged)
    assert sync == pipe, policy
    assert mp["requests_finished"] == len(prompts)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert ms["tokens_emitted"] == mp["tokens_emitted"]


@pytest.mark.parametrize("drafter", ["model", "ngram", "self"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("policy", ["static", "dsde"])
def test_pipelined_matches_sync_at_temperature(small_pair, policy, paged,
                                               drafter):
    """Identity-threaded RNG (DESIGN.md §7): at temperature 1.0 the
    sampled token streams are ALSO byte-identical between the sync and
    pipelined schedules — every draw is keyed by (request seed, the
    request's own round ordinal, purpose, position), never by host
    dispatch order, batch composition, or bucket width; stochastic
    pipelined rounds dispatch at the policy's max bucket so a stale
    bucket pick can never clip a proposal window.  Covers slot reuse
    (3 requests, 2 slots) for both KV layouts and a model-free
    drafter."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(23)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in (7, 12, 5)]
    model_free = drafter != "model"
    spec = SpecDecodeConfig(policy=policy, temperature=1.0, drafter=drafter)
    outs = {}
    for pipelined in (False, True):
        sv = ServingConfig(max_batch_size=2, max_seq_len=128,
                           paged_kv=paged, kv_block_size=16,
                           pipelined=pipelined)
        eng = ServingEngine(pt, cfg, None if model_free else pd,
                            None if model_free else cfg, spec, sv, seed=3)
        reqs = [Request(i, prompt=p, max_new_tokens=10)
                for i, p in enumerate(prompts)]
        m = eng.run(reqs)
        assert m["requests_finished"] == len(prompts)
        outs[pipelined] = [r.output for r in reqs]
    assert outs[False] == outs[True], (policy, paged, drafter)


def test_pipelined_exact_under_forced_preemption(small_pair):
    """Pool pressure during the pipelined window: growth planned from
    stale mirrors must evict-and-requeue (never under-allocate), and
    recompute-on-readmit must reproduce the dense sync stream exactly —
    including the emitted tokens of the round the victim was still part
    of when it was evicted."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in (30, 25, 20)]
    dense, _, _, _ = _run(cfg, pt, pd, "dsde", pipelined=False,
                          prompts=prompts, max_new=40, bs=8)
    pipe, m, _, _ = _run(cfg, pt, pd, "dsde", pipelined=True,
                         prompts=prompts, paged=True, max_new=40, bs=8,
                         nblocks=16)
    assert m["preemptions"] >= 1
    assert m["requests_finished"] == 3
    assert dense == pipe


# ---------------------------------------------------------------------------
# Device-side termination edge cases
# ---------------------------------------------------------------------------

def _round_boundaries(eng):
    """Cumulative emitted-token count after each round of a batch-1 run,
    offset by the prefill token (position 0 of the output)."""
    cum, out = 1, []
    for r in eng.round_log:
        cum += int(r["emitted"])
        out.append(cum)
    return out


def test_eos_exactly_on_round_boundary(small_pair):
    """An EOS that is the LAST emitted token of a round must finish the
    request without touching the next round's (already dispatched, in
    the pipelined case) work — and the streams must still match sync."""
    cfg, pt, pd = small_pair
    prompt = list(range(2, 10))
    base, _, _, eng = _run(cfg, pt, pd, "static", pipelined=False,
                           prompts=[prompt], max_new=32, batch=1)
    stream = base[0]
    # pick a round boundary whose token value does not occur earlier
    pick = None
    for cum in _round_boundaries(eng):
        p = cum - 1
        if 0 < p < len(stream) and stream[p] not in stream[:p]:
            pick = p
            break
    assert pick is not None, "no usable boundary in this rollout"
    eos = stream[pick]
    want = stream[:pick + 1]
    for pipelined in (False, True):
        got, _, reqs, _ = _run(cfg, pt, pd, "static", pipelined=pipelined,
                               prompts=[prompt], max_new=32, batch=1,
                               eos=eos)
        assert got[0] == want, pipelined
        assert reqs[0].state == RequestState.FINISHED


def test_max_new_tokens_truncates_mid_round(small_pair):
    """A budget that runs out mid-round with accepted tokens beyond it
    must truncate the emission at exactly max_new_tokens — the device
    must not over-emit even though the rejection sampler accepted
    more."""
    cfg, pt, pd = small_pair
    prompt = list(range(3, 11))
    base, _, _, eng = _run(cfg, pt, pd, "static", pipelined=False,
                           prompts=[prompt], max_new=32, batch=1)
    stream = base[0]
    bounds = _round_boundaries(eng)
    # a budget strictly inside a round that emitted >= 2 tokens
    pick = next((b - 1 for b, prev in zip(bounds, [1] + bounds)
                 if b - prev >= 2 and b - 1 > 1), None)
    assert pick is not None, "no multi-token round in this rollout"
    for pipelined in (False, True):
        got, m, reqs, _ = _run(cfg, pt, pd, "static", pipelined=pipelined,
                               prompts=[prompt], max_new=pick, batch=1)
        assert len(got[0]) == pick, pipelined       # never over-emits
        assert got[0] == stream[:pick]
        assert reqs[0].state == RequestState.FINISHED
        assert m["tokens_emitted"] == pick


def test_finished_slot_readmitted_in_pipelined_window(small_pair):
    """More requests than slots with tiny budgets: every finish frees a
    slot that is re-admitted while the trailing round — which still
    carries the finished request's (device-dead) row — is in flight.
    The new occupant must start cleanly (fresh done/budget/EOS rows) and
    the whole stream set must match the synchronous engine."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, size=6).tolist()
               for _ in range(6)]
    sync, ms, _, _ = _run(cfg, pt, pd, "dsde", pipelined=False,
                          prompts=prompts, max_new=5, batch=2)
    pipe, mp, reqs, _ = _run(cfg, pt, pd, "dsde", pipelined=True,
                             prompts=prompts, max_new=5, batch=2)
    assert sync == pipe
    assert mp["requests_finished"] == 6
    assert all(len(r.output) == 5 for r in reqs)
    # the pipelined schedule re-used both slots repeatedly
    assert ms["rounds"] >= 3 and mp["rounds"] >= ms["rounds"]


def test_preempted_finished_at_first_token_never_readmitted(small_pair):
    """Regression (zombie requeue): a request that FINISHES at its
    prefill-sampled first token but is preempted before that token is
    reconciled must be dropped from the requeue at reconciliation —
    releasing it would no-op on the empty slot, and the FINISHED request
    would be readmitted as a permanently-dead device row, hanging
    ``run()``.  Pool sized so the older request's first growth (which
    carries the in-flight staleness slack) evicts the young 1-token
    request exactly one plan after both were admitted together: the
    young one's admission leaves just the two blocks of headroom the
    older one is owed."""
    cfg, pt, pd = small_pair
    a = Request(0, prompt=list(range(1, 102)), max_new_tokens=12)  # 13 blocks
    b = Request(1, prompt=list(range(1, 9)), max_new_tokens=1)     # 1 block
    first_b = greedy_rollout(pt, cfg, b.prompt, 1)
    spec = SpecDecodeConfig(policy="dsde", temperature=0.0)
    sv = ServingConfig(max_batch_size=2, max_seq_len=128, paged_kv=True,
                       kv_block_size=8, num_kv_blocks=16, pipelined=True)
    eng = ServingEngine(pt, cfg, pd, cfg, spec, sv, seed=0)
    m = eng.run([a, b], max_rounds=40)      # bounded: a hang would loop
    assert b.preemptions >= 1               # the scenario actually occurred
    assert m["requests_finished"] == 2
    assert b.state == RequestState.FINISHED and b.output == first_b
    assert a.state == RequestState.FINISHED and len(a.output) == 12
    assert not eng.scheduler.has_work()


def test_eos_as_first_token_finishes_without_host_sync(small_pair):
    """A prefill-sampled first token that is already EOS (or a 1-token
    budget) must terminate device-side: the pipelined engine dispatches
    a round containing the slot before the host ever sees the token."""
    cfg, pt, pd = small_pair
    prompt = list(range(2, 10))
    first = greedy_rollout(pt, cfg, prompt, 1)[0]
    for pipelined in (False, True):
        got, _, reqs, _ = _run(cfg, pt, pd, "static", pipelined=pipelined,
                               prompts=[prompt], max_new=32, batch=1,
                               eos=first)
        assert got[0] == [first], pipelined
        assert reqs[0].state == RequestState.FINISHED
    for pipelined in (False, True):
        got, _, reqs, _ = _run(cfg, pt, pd, "static", pipelined=pipelined,
                               prompts=[prompt], max_new=1, batch=1)
        assert got[0] == [first], pipelined
        assert reqs[0].state == RequestState.FINISHED


# ---------------------------------------------------------------------------
# Accounting: round log masking, serving metrics, batched prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipelined", [False, True], ids=["sync", "pipe"])
def test_round_log_accounting_consistent(small_pair, pipelined):
    """emitted / accepted / proposed are all masked by the same live-row
    set, so the whole-run identities hold exactly: every emitted token is
    either a prefill first token or counted in some round's ``emitted``,
    and greedy emission is accepted + one bonus per live row."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, cfg.vocab_size, size=8).tolist()
               for _ in range(4)]
    _, m, reqs, eng = _run(cfg, pt, pd, "dsde", pipelined=pipelined,
                           prompts=prompts, max_new=12, batch=2)
    per_round = [r["emitted"] for r in eng.round_log]
    assert m["tokens_emitted"] == sum(per_round) + len(reqs)
    for r in eng.round_log:
        assert r["accepted"] <= r["proposed"]
        # emitted = accepted + (one bonus per live row), minus any
        # device-side EOS/budget truncation — never more
        assert r["emitted"] <= r["accepted"] + len(prompts)
        assert r["host_blocked_s"] >= 0.0
        assert r["wall_s"] > 0.0


@pytest.mark.parametrize("pipelined", [False, True], ids=["sync", "pipe"])
def test_serving_metrics_ttft_and_queue_wait(small_pair, pipelined):
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cfg.vocab_size, size=6).tolist()
               for _ in range(5)]
    _, m, reqs, _ = _run(cfg, pt, pd, "dsde", pipelined=pipelined,
                         prompts=prompts, max_new=8, batch=2)
    assert np.isfinite(m["ttft_mean_s"]) and m["ttft_mean_s"] >= 0.0
    assert np.isfinite(m["ttft_p95_s"]) and m["ttft_p95_s"] >= m["ttft_mean_s"] * 0.5
    assert np.isfinite(m["queue_wait_mean_s"]) and m["queue_wait_mean_s"] >= 0.0
    assert m["host_blocked_s"] >= 0.0
    for r in reqs:
        assert r.admit_time is not None and r.admit_time >= r.arrival_time
        assert r.first_dispatch_time is not None
        assert r.first_token_time is not None
        # the host observes the first token at reconciliation, never
        # before the prefill that produced it was dispatched
        assert r.first_token_time >= r.first_dispatch_time
        assert r.ttft() >= r.queue_wait()


@pytest.mark.parametrize("drafter,programs", [("model", 4), ("ngram", 2)],
                         ids=["model", "ngram"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_batched_prefill_one_program_per_bucket(small_pair, monkeypatch,
                                                paged, drafter, programs):
    """Requests admitted together that share a prompt bucket prefill in
    ONE multi-row program per model (2 jit calls per group with a model
    drafter — target + draft — not 2 per request; distinct buckets form
    distinct groups).  Model-free drafters skip the draft prefill
    program entirely: 1 call per group."""
    import repro.core.prefill as prefill_mod
    cfg, pt, pd = small_pair
    calls = []
    name = "prefill_paged_rows" if paged else "prefill_rows"
    orig = getattr(prefill_mod, name)

    def spy(*args, **kw):
        calls.append(1)
        return orig(*args, **kw)

    monkeypatch.setattr(prefill_mod, name, spy)
    spec = SpecDecodeConfig(policy="static", temperature=0.0,
                            drafter=drafter)
    sv = ServingConfig(max_batch_size=4, max_seq_len=128, paged_kv=paged,
                       kv_block_size=16)
    model_free = drafter != "model"
    eng = ServingEngine(pt, cfg, None if model_free else pd,
                        None if model_free else cfg, spec, sv, seed=0)
    # three same-bucket prompts (<=16 tokens) + one bucket-64 prompt
    for i, n in enumerate((5, 9, 12, 40)):
        eng.submit(Request(i, prompt=list(range(1, n + 1)),
                           max_new_tokens=4))
    eng.step()
    assert sum(calls) == programs   # 2 buckets x models prefilled
    while eng.scheduler.has_work():
        eng.step()


def test_pipelined_step_api_still_synchronous(small_pair):
    """step() stays the lockstep entry point even on an engine whose
    config enables pipelining — drivers that single-step (benchmarks,
    tests) keep exact sync semantics."""
    cfg, pt, pd = small_pair
    prompt = list(range(1, 9))
    ref = greedy_rollout(pt, cfg, prompt, 8)
    spec = SpecDecodeConfig(policy="static", temperature=0.0)
    sv = ServingConfig(max_batch_size=1, max_seq_len=128, pipelined=True)
    eng = ServingEngine(pt, cfg, pd, cfg, spec, sv, seed=0)
    req = Request(0, prompt=prompt, max_new_tokens=8)
    eng.submit(req)
    while eng.scheduler.has_work():
        eng.step()
    assert req.output == ref
