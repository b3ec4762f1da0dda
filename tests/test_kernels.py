"""Pallas kernel validation (interpret mode on CPU): shape/dtype sweeps
against the pure-jnp oracles in repro.kernels.ref, plus the flash-attention
custom-VJP fallback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.kld_accept import fused_kld_accept
from repro.kernels.ops import kld_accept_signals, ragged_attention
from repro.kernels.ragged_attention import ragged_verify_attention
from repro.models.flash import flash_attend
from repro.models.layers import attend

jax.config.update("jax_platform_name", "cpu")
KEY = jax.random.PRNGKey(0)


def _attn_inputs(b, t, h, kv, d, w, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, t, h, d)).astype(dtype)
    kb = jax.random.normal(ks[1], (b, w, kv, d)).astype(dtype)
    vb = jax.random.normal(ks[2], (b, w, kv, d)).astype(dtype)
    lens = jax.random.randint(ks[3], (b,), t, max(w - t, t + 1))
    q_pos = lens[:, None] + jnp.arange(t)[None]
    kv_pos = jnp.where(jnp.arange(w)[None] < (lens[:, None] + t),
                       jnp.arange(w)[None], -1)
    return q, kb, vb, q_pos, kv_pos


# ---------------------------------------------------------------------------
# ragged verification attention kernel
# ---------------------------------------------------------------------------

SHAPES = [
    (2, 1, 8, 2, 64, 128),      # plain decode, GQA 4x
    (3, 6, 8, 8, 64, 256),      # verify, MHA
    (2, 11, 12, 4, 128, 96),    # verify, SL_max+1 queries
    (1, 4, 4, 1, 32, 512),      # MQA
    (2, 3, 16, 16, 64, 160),    # non-pow2 ring
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("window", [None, 64])
def test_ragged_attention_kernel_vs_oracle(shape, window):
    b, t, h, kv, d, w = shape
    q, kb, vb, q_pos, kv_pos = _attn_inputs(b, t, h, kv, d, w, jnp.float32)
    out = ragged_verify_attention(q, kb, vb, q_pos, kv_pos, window=window,
                                  interpret=True, block_k=64)
    want = ref.ragged_verify_attention_ref(q, kb, vb, q_pos, kv_pos,
                                           window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
def test_ragged_attention_dtypes(dtype, atol):
    q, kb, vb, q_pos, kv_pos = _attn_inputs(2, 4, 8, 4, 64, 128, dtype)
    out = ragged_verify_attention(q, kb, vb, q_pos, kv_pos, interpret=True,
                                  block_k=64)
    want = ref.ragged_verify_attention_ref(q, kb, vb, q_pos, kv_pos)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=atol, rtol=1e-2)


def test_ragged_attention_empty_cache_rows():
    """Sequences whose ring has only the freshly-written tokens."""
    b, t, h, kv, d, w = 2, 2, 4, 2, 32, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    kb = jax.random.normal(ks[1], (b, w, kv, d))
    vb = jax.random.normal(ks[2], (b, w, kv, d))
    q_pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    kv_pos = jnp.where(jnp.arange(w)[None] < t, jnp.arange(w)[None], -1)
    out = ragged_verify_attention(q, kb, vb, q_pos, kv_pos, interpret=True,
                                  block_k=32)
    want = ref.ragged_verify_attention_ref(q, kb, vb, q_pos, kv_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_ops_dispatch_cpu_uses_ref():
    q, kb, vb, q_pos, kv_pos = _attn_inputs(1, 2, 4, 2, 32, 64, jnp.float32)
    out = ragged_attention(q, kb, vb, q_pos, kv_pos)
    want = ref.ragged_verify_attention_ref(q, kb, vb, q_pos, kv_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


# ---------------------------------------------------------------------------
# fused KLD / acceptance kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,v,bv", [(2, 3, 1000, 256), (4, 11, 2048, 512),
                                      (1, 1, 5003, 512), (3, 2, 640, 640)])
def test_fused_kld_vs_oracle(b, t, v, bv):
    ks = jax.random.split(KEY, 3)
    tl = jax.random.normal(ks[0], (b, t, v)) * 3
    dl = jax.random.normal(ks[1], (b, t, v)) * 3
    tok = jax.random.randint(ks[2], (b, t), 0, v)
    got = fused_kld_accept(tl, dl, tok, block_v=bv, interpret=True)
    want = ref.kld_accept_ref(tl, dl, tok)
    for g, w, name in zip(got, want, ("kld", "ent", "ptok", "qtok")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


@given(st.integers(0, 1000), st.integers(2, 6), st.sampled_from([128, 384]))
@settings(max_examples=15, deadline=None)
def test_fused_kld_property_sweep(seed, t, v):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    tl = jax.random.normal(ks[0], (1, t, v)) * 2
    dl = jax.random.normal(ks[1], (1, t, v)) * 2
    tok = jax.random.randint(ks[2], (1, t), 0, v)
    kld, ent, ptok, qtok = fused_kld_accept(tl, dl, tok, block_v=128,
                                            interpret=True)
    assert bool((kld >= 0).all())
    assert bool((ent >= 0).all())
    assert bool((ptok >= 0).all()) and bool((ptok <= 1 + 1e-6).all())
    assert bool((qtok >= 0).all()) and bool((qtok <= 1 + 1e-6).all())


def test_ops_kld_dispatch():
    ks = jax.random.split(KEY, 3)
    tl = jax.random.normal(ks[0], (1, 2, 300))
    dl = jax.random.normal(ks[1], (1, 2, 300))
    tok = jax.random.randint(ks[2], (1, 2), 0, 300)
    got = kld_accept_signals(tl, dl, tok)
    want = ref.kld_accept_ref(tl, dl, tok)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention custom-VJP fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,window,causal", [(48, None, True), (64, 24, True),
                                             (50, None, False)])
def test_flash_forward_and_grads(t, window, causal):
    b, h, kv, d = 2, 8, 8, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, kv, d))
    v = jax.random.normal(ks[2], (b, t, kv, d))
    qp = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    valid = jnp.ones((b, t), bool)

    f = lambda *a: (flash_attend(*a, kv_valid=None, window=window,
                                 causal=causal, q_block=16, kv_block=16)
                    ** 2).sum()
    g = lambda *a: (attend(*a, q_pos=qp, kv_pos=qp, kv_valid=valid,
                           window=window, causal=causal) ** 2).sum()
    g1 = jax.grad(f, (0, 1, 2))(q, k, v)
    g2 = jax.grad(g, (0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4)


def test_flash_ragged_validity():
    """kv_valid masking (ragged prompts) agrees with naive attention."""
    b, t, h, d = 2, 40, 4, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, h, d))
    v = jax.random.normal(ks[2], (b, t, h, d))
    valid = jnp.arange(t)[None] < jnp.array([[25], [33]])
    qp = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    o1 = flash_attend(q, k, v, kv_valid=valid, q_block=16, kv_block=16)
    o2 = attend(q, k, v, q_pos=qp, kv_pos=qp, kv_valid=valid)
    # compare only valid query rows (invalid rows are don't-care)
    m = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(o1)[m], np.asarray(o2)[m],
                               atol=1e-4)
