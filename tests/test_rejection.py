"""Rejection-sampler correctness: the heart of speculative decoding's
exactness guarantee (Leviathan et al.), including the ragged per-sequence
lengths of paper §3.2."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.rejection import rejection_sample

jax.config.update("jax_platform_name", "cpu")

V = 16
PAD = V


def _logits(key, b, n, scale=2.0):
    return jax.random.normal(key, (b, n, V + 0)) * scale


def test_greedy_accepts_iff_argmax_matches():
    key = jax.random.PRNGKey(0)
    tl = _logits(key, 1, 4)
    # draft tokens: first matches argmax, second doesn't
    am = jnp.argmax(tl[:, :3], -1)
    draft = am.at[0, 1].set((am[0, 1] + 1) % V)
    dl = tl[:, :3]  # draft distribution irrelevant at temp 0
    r = rejection_sample(key, draft, dl, tl, jnp.array([3]),
                         temperature=0.0, vocab_size=V, pad_id=PAD)
    assert int(r.num_accepted[0]) == 1
    # recovery token = target argmax at the rejected position
    assert int(r.next_token[0]) == int(am[0, 1])


def test_greedy_full_acceptance_bonus():
    key = jax.random.PRNGKey(1)
    tl = _logits(key, 1, 4)
    am = jnp.argmax(tl, -1)
    r = rejection_sample(key, am[:, :3], tl[:, :3], tl, jnp.array([3]),
                         temperature=0.0, vocab_size=V, pad_id=PAD)
    assert int(r.num_accepted[0]) == 3
    assert int(r.next_token[0]) == int(am[0, 3])   # bonus from position K
    np.testing.assert_array_equal(np.asarray(r.emitted[0]),
                                  np.asarray(jnp.concatenate([am[0, :3],
                                                              am[0, 3:4]])))


def test_ragged_draft_lengths():
    key = jax.random.PRNGKey(2)
    tl = _logits(key, 3, 5)
    am = jnp.argmax(tl, -1)
    draft = am[:, :4]
    lens = jnp.array([0, 2, 4])
    r = rejection_sample(key, draft, tl[:, :4], tl, lens,
                         temperature=0.0, vocab_size=V, pad_id=PAD)
    # acceptance never exceeds the per-sequence draft length
    assert np.all(np.asarray(r.num_accepted) <= np.asarray(lens))
    assert int(r.num_accepted[0]) == 0   # nothing proposed
    assert np.all(np.asarray(r.num_emitted) == np.asarray(r.num_accepted) + 1)
    # pad id fills beyond the emitted prefix
    em = np.asarray(r.emitted)
    for b in range(3):
        assert np.all(em[b, int(r.num_emitted[b]):] == PAD)


def test_zero_draft_autoregressive():
    key = jax.random.PRNGKey(3)
    tl = _logits(key, 2, 1)
    r = rejection_sample(key, jnp.zeros((2, 0), jnp.int32),
                         jnp.zeros((2, 0, V)), tl, jnp.zeros((2,), jnp.int32),
                         temperature=0.0, vocab_size=V, pad_id=PAD)
    assert np.all(np.asarray(r.num_emitted) == 1)
    np.testing.assert_array_equal(np.asarray(r.next_token),
                                  np.asarray(jnp.argmax(tl[:, 0], -1)))


@pytest.mark.parametrize("seed", [0, 1])
def test_distribution_preservation(seed):
    """THE speculative-decoding invariant: with one draft token, the emitted
    first token is distributed exactly as the target distribution,
    regardless of the draft distribution."""
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    v = 8
    tl = jax.random.normal(k1, (1, 2, v)) * 1.5   # target logits
    dl = jax.random.normal(k2, (1, 1, v)) * 1.5   # divergent draft
    p_target = np.asarray(jax.nn.softmax(tl[0, 0]))
    q_draft = jax.nn.softmax(dl[0, 0])

    n = 30000
    counts = np.zeros(v)
    keys = jax.random.split(k3, n)

    def one(key):
        kd, kr = jax.random.split(key)
        d = jax.random.categorical(kd, jnp.log(q_draft))[None, None]
        r = rejection_sample(kr, d.astype(jnp.int32), dl, tl,
                             jnp.array([1]), temperature=1.0,
                             vocab_size=v, pad_id=v)
        return r.emitted[0, 0]

    toks = np.asarray(jax.vmap(one)(keys))
    for t in toks:
        counts[t] += 1
    freq = counts / n
    # total-variation distance should be ~ sampling noise
    tv = 0.5 * np.abs(freq - p_target).sum()
    assert tv < 0.02, (tv, freq, p_target)


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_accepted_prefix_property(seed):
    """accept_mask is always a prefix (no holes) and consistent with
    num_accepted."""
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    b, k = 3, 5
    tl = jax.random.normal(k1, (b, k + 1, V))
    dl = jax.random.normal(k2, (b, k, V))
    draft = jax.random.randint(k3, (b, k), 0, V)
    lens = jax.random.randint(k4, (b,), 0, k + 1)
    r = rejection_sample(k5, draft, dl, tl, lens, temperature=1.0,
                         vocab_size=V, pad_id=PAD)
    m = np.asarray(r.accept_mask)
    na = np.asarray(r.num_accepted)
    for i in range(b):
        assert m[i, :na[i]].all()
        assert not m[i, na[i]:].any()
        assert na[i] <= int(lens[i])
        # emitted tokens are in-vocab up to num_emitted
        em = np.asarray(r.emitted[i])
        assert (em[:na[i] + 1] < V).all()
