"""Unit + property tests for the KLD stability signals (paper Eq. 4-7)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.signals import (KLDHistory, decay_weights, draft_entropy,
                                kld_per_position, weighted_mean, weighted_var,
                                wvir)

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Eq. (5)-(7): weighted statistics
# ---------------------------------------------------------------------------

def test_decay_weights_most_recent_largest():
    w = np.asarray(decay_weights(5, 0.85))
    # oldest-first layout: last entry is the most recent, alpha_1 = 1
    assert w[-1] == pytest.approx(1.0)
    assert np.all(np.diff(w) > 0)
    assert w[0] == pytest.approx(0.85 ** 4)


def test_weighted_mean_matches_hand_computation():
    # N=3 values chronological [2, 4, 6], delta=0.5
    # alpha (oldest-first) = [0.25, 0.5, 1.0]
    x = jnp.array([2.0, 4.0, 6.0])
    w = decay_weights(3, 0.5)
    mu = float(weighted_mean(x, w))
    expect = (0.25 * 2 + 0.5 * 4 + 1.0 * 6) / 1.75
    assert mu == pytest.approx(expect, rel=1e-6)


def test_weighted_var_matches_hand_computation():
    x = jnp.array([1.0, 3.0])
    w = decay_weights(2, 0.5)          # [0.5, 1.0]
    mu = (0.5 * 1 + 1.0 * 3) / 1.5
    expect = (0.5 * (1 - mu) ** 2 + 1.0 * (3 - mu) ** 2) / 1.5
    assert float(weighted_var(x, w)) == pytest.approx(expect, rel=1e-6)


@given(st.lists(st.floats(0.0, 10.0), min_size=2, max_size=30),
       st.floats(0.5, 0.99))
@settings(max_examples=50, deadline=None)
def test_weighted_var_nonnegative_and_zero_for_constant(vals, delta):
    x = jnp.asarray(vals, jnp.float32)
    w = decay_weights(len(vals), delta)
    v = float(weighted_var(x, w))
    assert v >= -1e-6
    c = jnp.full((len(vals),), 3.14, jnp.float32)
    assert float(weighted_var(c, w)) == pytest.approx(0.0, abs=1e-9)


@given(st.lists(st.floats(0.01, 10.0), min_size=3, max_size=20),
       st.floats(0.1, 5.0))
@settings(max_examples=30, deadline=None)
def test_weighted_var_scales_quadratically(vals, c):
    x = jnp.asarray(vals, jnp.float32)
    w = decay_weights(len(vals), 0.85)
    v1 = float(weighted_var(x, w))
    v2 = float(weighted_var(c * x, w))
    assert v2 == pytest.approx(c * c * v1, rel=1e-3, abs=1e-6)


# ---------------------------------------------------------------------------
# KLD / entropy signals
# ---------------------------------------------------------------------------

def test_kld_zero_for_identical_distributions():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 50))
    kld = kld_per_position(logits, logits)
    assert float(jnp.abs(kld).max()) < 1e-5


def test_kld_positive_for_different_distributions():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (2, 4, 50)) * 3
    b = jax.random.normal(k2, (2, 4, 50)) * 3
    assert float(kld_per_position(a, b).min()) > 0


def test_kld_respects_validity_mask():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (1, 4, 20))
    b = jax.random.normal(k2, (1, 4, 20))
    valid = jnp.array([[True, False, True, False]])
    kld = kld_per_position(a, b, valid)
    assert kld[0, 1] == 0.0 and kld[0, 3] == 0.0
    assert kld[0, 0] > 0 and kld[0, 2] > 0


def test_entropy_uniform_is_log_v():
    v = 64
    logits = jnp.zeros((1, 1, v))
    assert float(draft_entropy(logits)[0, 0]) == pytest.approx(np.log(v),
                                                               rel=1e-5)


# ---------------------------------------------------------------------------
# History ring buffer + WVIR (Eq. 4, Fig. 5)
# ---------------------------------------------------------------------------

def test_history_chronological_order():
    h = KLDHistory.init(1, 5)
    for i in range(7):
        h = h.push(jnp.array([float(i)]))
    vals, valid = h.chronological(5)
    np.testing.assert_array_equal(np.asarray(vals[0]), [2, 3, 4, 5, 6])
    assert bool(valid.all())


def test_history_validity_before_fill():
    h = KLDHistory.init(1, 6)
    h = h.push(jnp.array([1.0]))
    h = h.push(jnp.array([2.0]))
    vals, valid = h.chronological(4)
    np.testing.assert_array_equal(np.asarray(valid[0]),
                                  [False, False, True, True])
    assert float(vals[0, 2]) == 1.0 and float(vals[0, 3]) == 2.0


def test_history_inactive_rows_frozen():
    h = KLDHistory.init(2, 4)
    h = h.push(jnp.array([1.0, 9.0]), active=jnp.array([True, False]))
    assert int(h.count[0]) == 1 and int(h.count[1]) == 0


def test_wvir_neutral_until_enough_history():
    h = KLDHistory.init(1, 30)
    for i in range(5):
        h = h.push(jnp.array([float(i)]))
    assert float(wvir(h, 10, 30, 0.85)[0]) == 1.0


def test_wvir_detects_instability():
    """Stable history then a sudden spike -> short-term variance outgrows
    long-term variance (the paper's 'growing instability' indicator)."""
    h = KLDHistory.init(1, 30)
    rng = np.random.RandomState(0)
    for _ in range(30):
        h = h.push(jnp.array([1.0 + 0.01 * rng.randn()]))
    stable = float(wvir(h, 10, 30, 0.85)[0])
    for v in (4.0, 0.2, 5.0, 0.1):   # violent swings
        h = h.push(jnp.array([v]))
    unstable = float(wvir(h, 10, 30, 0.85)[0])
    assert unstable > stable
    assert unstable > 1.0


@given(st.floats(0.5, 4.0))
@settings(max_examples=20, deadline=None)
def test_wvir_scale_invariant(scale):
    """Var ratio is invariant to rescaling the whole KLD history."""
    h1 = KLDHistory.init(1, 30)
    h2 = KLDHistory.init(1, 30)
    rng = np.random.RandomState(1)
    for _ in range(35):
        v = abs(1.0 + rng.randn())
        h1 = h1.push(jnp.array([v]))
        h2 = h2.push(jnp.array([v * scale]))
    w1 = float(wvir(h1, 10, 30, 0.85)[0])
    w2 = float(wvir(h2, 10, 30, 0.85)[0])
    assert w1 == pytest.approx(w2, rel=1e-3)
