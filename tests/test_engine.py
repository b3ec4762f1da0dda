"""End-to-end serving engine tests — including the exactness guarantee:
greedy speculative decoding must emit exactly the target model's greedy
rollout, no matter how bad the draft is."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import ServingConfig, SpecDecodeConfig
from repro.models.module import init_params
from repro.models.transformer import forward, model_specs
from repro.models import cache as cache_lib
from repro.serving.engine import ServingEngine
from repro.serving.request import Request

jax.config.update("jax_platform_name", "cpu")

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small_pair():
    cfg = get_config("smollm-135m").reduced()
    pt = init_params(model_specs(cfg), jax.random.PRNGKey(1), jnp.float32)
    noise = init_params(model_specs(cfg), jax.random.PRNGKey(7), jnp.float32)
    pd = jax.tree_util.tree_map(lambda a, b: a + 0.05 * b, pt, noise)
    return cfg, pt, pd


def greedy_rollout(params, cfg, prompt, n):
    """Reference: plain greedy autoregressive decoding via full forwards."""
    toks = list(prompt)
    for _ in range(n):
        logits, _, _ = forward(params, cfg,
                               jnp.asarray([toks], jnp.int32), mode="train")
        nxt = int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))
        toks.append(nxt)
    return toks[len(prompt):]


@pytest.mark.parametrize("policy", ["dsde", "static", "adaedl"])
def test_greedy_spec_decode_exactness(small_pair, policy):
    """Greedy spec decoding == greedy target rollout, token for token."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in (7, 12, 5)]
    n_new = 24
    refs = [greedy_rollout(pt, cfg, p, n_new) for p in prompts]

    spec = SpecDecodeConfig(policy=policy, temperature=0.0)
    eng = ServingEngine(pt, cfg, pd, cfg, spec,
                        ServingConfig(max_batch_size=2, max_seq_len=128),
                        seed=0)
    reqs = [Request(i, prompt=p, max_new_tokens=n_new)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    for req, ref in zip(reqs, refs):
        assert req.output == ref, (req.request_id, req.output, ref)


def test_autoregressive_baseline_exactness(small_pair):
    cfg, pt, pd = small_pair
    prompt = list(range(1, 9))
    ref = greedy_rollout(pt, cfg, prompt, 12)
    spec = SpecDecodeConfig(policy="autoregressive", temperature=0.0)
    eng = ServingEngine(pt, cfg, pd, cfg, spec,
                        ServingConfig(max_batch_size=2, max_seq_len=128))
    req = Request(0, prompt=prompt, max_new_tokens=12)
    m = eng.run([req])
    assert req.output == ref
    # first token comes from prefill; every other token costs one round
    assert m["rounds"] == 11
    assert m["block_efficiency"] == pytest.approx(12 / 11)


def test_spec_decode_faster_than_autoregressive(small_pair):
    """With a correlated draft, spec decoding must use fewer rounds."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, size=10).tolist()
               for _ in range(4)]

    def run(policy):
        spec = SpecDecodeConfig(policy=policy, temperature=0.0)
        eng = ServingEngine(pt, cfg, pd, cfg, spec,
                            ServingConfig(max_batch_size=4, max_seq_len=128))
        reqs = [Request(i, prompt=p, max_new_tokens=24) for i, p in
                enumerate(prompts)]
        return eng.run(reqs)

    m_sp = run("static")
    m_ar = run("autoregressive")
    assert m_sp["rounds"] < m_ar["rounds"]
    assert m_sp["block_efficiency"] > 1.0


def test_continuous_batching_reuses_slots(small_pair):
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(2)
    spec = SpecDecodeConfig(policy="dsde", temperature=0.0)
    eng = ServingEngine(pt, cfg, pd, cfg, spec,
                        ServingConfig(max_batch_size=2, max_seq_len=128))
    reqs = [Request(i, prompt=rng.randint(0, cfg.vocab_size, size=6).tolist(),
                    max_new_tokens=8) for i in range(5)]
    m = eng.run(reqs)
    assert m["requests_finished"] == 5
    assert all(r.done for r in reqs)
    assert all(len(r.output) == 8 for r in reqs)


def test_eos_stops_early(small_pair):
    cfg, pt, pd = small_pair
    prompt = list(range(2, 10))
    ref = greedy_rollout(pt, cfg, prompt, 32)
    eos = ref[5]   # force an early EOS at a token we know will appear
    spec = SpecDecodeConfig(policy="static", temperature=0.0)
    eng = ServingEngine(pt, cfg, pd, cfg, spec,
                        ServingConfig(max_batch_size=1, max_seq_len=128))
    req = Request(0, prompt=prompt, max_new_tokens=32, eos_token_id=eos)
    eng.run([req])
    assert req.output[-1] == eos
    assert len(req.output) <= 32
    assert req.output == ref[:len(req.output)]


def test_prompt_bucket_clamped_to_kv_budget(small_pair):
    """Regression: a prompt whose power-of-two bucket rounds past
    max_seq_len used to build a prefill program wider than the cache —
    write_kv then silently dropped the prompt's leading tokens."""
    from repro.serving.engine import _bucket
    assert _bucket(33, cap=48) == 48
    assert _bucket(33, cap=128) == 64
    assert _bucket(5, cap=48) == 16
    cfg, pt, pd = small_pair
    prompt = list(range(1, 34))          # 33 tokens -> bucket 64 > 48
    ref_out = greedy_rollout(pt, cfg, prompt, 8)
    spec = SpecDecodeConfig(policy="autoregressive", temperature=0.0)
    eng = ServingEngine(pt, cfg, pd, cfg, spec,
                        ServingConfig(max_batch_size=1, max_seq_len=48))
    req = Request(0, prompt=prompt, max_new_tokens=8)
    eng.run([req])
    assert req.output == ref_out


def test_sampling_temperature_runs(small_pair):
    """Stochastic sampling path (temp 1.0) produces in-vocab tokens and
    respects max_new_tokens."""
    cfg, pt, pd = small_pair
    spec = SpecDecodeConfig(policy="dsde", temperature=1.0)
    eng = ServingEngine(pt, cfg, pd, cfg, spec,
                        ServingConfig(max_batch_size=2, max_seq_len=128))
    rng = np.random.RandomState(3)
    reqs = [Request(i, prompt=rng.randint(0, cfg.vocab_size, size=6).tolist(),
                    max_new_tokens=16) for i in range(3)]
    eng.run(reqs)
    for r in reqs:
        assert len(r.output) == 16
        assert all(0 <= t < cfg.vocab_size for t in r.output)


def test_recurrent_family_engine_exactness():
    """Spec decoding with state rollback (SSM family) stays exact."""
    cfg = get_config("mamba2-130m").reduced()
    pt = init_params(model_specs(cfg), jax.random.PRNGKey(1), jnp.float32)
    noise = init_params(model_specs(cfg), jax.random.PRNGKey(9), jnp.float32)
    pd = jax.tree_util.tree_map(lambda a, b: a + 0.05 * b, pt, noise)
    prompt = list(range(3, 11))
    ref = greedy_rollout(pt, cfg, prompt, 16)
    spec = SpecDecodeConfig(policy="dsde", temperature=0.0)
    eng = ServingEngine(pt, cfg, pd, cfg, spec,
                        ServingConfig(max_batch_size=1, max_seq_len=128))
    req = Request(0, prompt=prompt, max_new_tokens=16)
    eng.run([req])
    assert req.output == ref


@pytest.mark.parametrize("pipelined,precision",
                         [(False, "high"), (True, "tensorfloat32")])
def test_engine_traces_at_its_matmul_precision(small_pair, monkeypatch,
                                               pipelined, precision):
    """Every program the engine traces (prefill and rounds, target and
    draft) runs its matmuls at ``ServingConfig.matmul_precision``; the
    model itself sets none.  (Each case takes its own precision, so its
    programs are traced afresh rather than found in jit's cache.)"""
    from repro.models import transformer
    cfg, pt, pd = small_pair
    seen = []
    lm_head = transformer._lm_head

    def recording(*args, **kwargs):
        seen.append(jax.config.jax_default_matmul_precision)
        return lm_head(*args, **kwargs)

    monkeypatch.setattr(transformer, "_lm_head", recording)
    eng = ServingEngine(pt, cfg, pd, cfg, SpecDecodeConfig(),
                        ServingConfig(max_batch_size=2, max_seq_len=64,
                                      pipelined=pipelined,
                                      matmul_precision=precision), seed=0)
    eng.run([Request(i, prompt=[3, 4, 5, 6 + i], max_new_tokens=6)
             for i in range(2)])
    assert seen and set(seen) == {precision}
    seen.clear()
    transformer.forward(pt, cfg, jnp.asarray([[3, 4]], jnp.int32))
    assert seen == [None]
