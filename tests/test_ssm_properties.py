"""Property tests for the sequence-mixer substrates: Mamba-2 SSD duality
(chunked == recurrent), RG-LRU scan equivalences, masked-step identity —
the invariants speculative commit/rollback relies on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.rglru import rglru_scan, rglru_step_scan, rglru_specs
from repro.models.ssm import ssd_chunked, ssd_recurrent
from repro.models.module import init_params
from repro.configs import get_config

jax.config.update("jax_platform_name", "cpu")
KEY = jax.random.PRNGKey(0)


def _ssd_inputs(seed, b, s, h, p, n):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    return x, dt, A, B, C


@given(st.integers(0, 500), st.sampled_from([4, 8, 16]),
       st.integers(5, 33))
@settings(max_examples=12, deadline=None)
def test_ssd_duality_chunked_equals_recurrent(seed, chunk, s):
    """The paper's state-space duality: the matmul (attention-like) chunked
    form and the linear recurrence compute the same function — for any
    chunk size, including non-divisible sequence lengths."""
    x, dt, A, B, C = _ssd_inputs(seed, 2, s, 3, 4, 5)
    h0 = jnp.zeros((2, 3, 4, 5), jnp.float32)
    y1, hf1 = ssd_chunked(x, dt, A, B, C, chunk)
    y2, hf2 = ssd_recurrent(x, dt, A, B, C, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(hf1), np.asarray(hf2),
                               atol=2e-4, rtol=1e-3)


def test_ssd_chunked_initial_state_continuation():
    """Processing [a|b] in two chunked calls == one call over the whole."""
    x, dt, A, B, C = _ssd_inputs(7, 1, 24, 2, 4, 3)
    y_full, hf_full = ssd_chunked(x, dt, A, B, C, chunk=8)
    y1, h1 = ssd_chunked(x[:, :10], dt[:, :10], A, B[:, :10], C[:, :10], 8)
    y2, h2 = ssd_chunked(x[:, 10:], dt[:, 10:], A, B[:, 10:], C[:, 10:], 8,
                         h0=h1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(hf_full),
                               atol=2e-4, rtol=1e-3)


def test_ssd_masked_steps_are_identities():
    """dt=0 masking (speculative commit / ragged prefill): masked steps must
    leave the state exactly unchanged and contribute nothing downstream."""
    x, dt, A, B, C = _ssd_inputs(11, 1, 8, 2, 4, 3)
    h0 = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 4, 3))
    mask = jnp.array([[1, 1, 1, 0, 0, 0, 0, 0]], jnp.float32)
    _, hf_masked = ssd_recurrent(x, dt, A, B, C, h0, update_mask=mask)
    _, hf_prefix = ssd_recurrent(x[:, :3], dt[:, :3], A, B[:, :3], C[:, :3],
                                 h0)
    np.testing.assert_allclose(np.asarray(hf_masked), np.asarray(hf_prefix),
                               atol=1e-6)


@pytest.fixture(scope="module")
def lru_params():
    cfg = get_config("recurrentgemma-2b").reduced()
    return init_params(rglru_specs(cfg), KEY, jnp.float32), cfg


def test_rglru_assoc_scan_equals_step_scan(lru_params):
    p, cfg = lru_params
    w = cfg.rglru.lru_width
    x = jax.random.normal(KEY, (2, 17, w)) * 0.5
    h0 = jax.random.normal(jax.random.PRNGKey(5), (2, w)) * 0.1
    hs1, hf1 = rglru_scan(p, x, h0)
    hs2, hf2 = rglru_step_scan(p, x, h0)
    np.testing.assert_allclose(np.asarray(hs1), np.asarray(hs2),
                               atol=2e-5, rtol=1e-4)


def test_rglru_masked_identity(lru_params):
    p, cfg = lru_params
    w = cfg.rglru.lru_width
    x = jax.random.normal(KEY, (1, 6, w)) * 0.5
    h0 = jax.random.normal(jax.random.PRNGKey(6), (1, w)) * 0.1
    mask = jnp.array([[1, 1, 0, 0, 0, 0]], jnp.float32)
    _, hf_m = rglru_step_scan(p, x, h0, update_mask=mask)
    _, hf_p = rglru_step_scan(p, x[:, :2], h0)
    np.testing.assert_allclose(np.asarray(hf_m), np.asarray(hf_p), atol=1e-6)


def test_rglru_decay_bounded(lru_params):
    """|a_t| <= 1 always (stability of the gated recurrence)."""
    from repro.models.rglru import _gates
    p, cfg = lru_params
    x = jax.random.normal(KEY, (2, 9, cfg.rglru.lru_width)) * 3
    a, b = _gates(p, x, None)
    assert float(jnp.abs(a).max()) <= 1.0 + 1e-6
