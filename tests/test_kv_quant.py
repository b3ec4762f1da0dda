"""Quantized KV serving data plane (DESIGN.md §13): int8 per-block
paged pools with per-slot-per-KV-head amax scales.

Covers the exactness contract layer by layer: quantization primitives,
the quantized pool struct + write/gather round trip, the Pallas
``paged_ragged_verify_attention_quant`` kernel against its jnp oracle,
bounded error against the fp pipeline, dtype-aware byte accounting at
the admission boundary, and the serving-level statistical exactness of
the stochastic path over a quantized pool (chi-square, both drafter
families)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import prefill as prefill_lib
from repro.core import spec_decode as sd
from repro.core.config import ModelConfig, ServingConfig, SpecDecodeConfig
from repro.kernels import ops, ref
from repro.kernels.ragged_attention import (
    paged_ragged_verify_attention, paged_ragged_verify_attention_quant)
from repro.models import cache as cache_lib
from repro.models.module import init_params
from repro.models.transformer import forward, model_specs
from repro.serving.engine import ServingEngine
from repro.serving.request import Request
from repro.serving.scheduler import LookaheadScheduler

from paged_inputs import paged_attn_inputs

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Quantization primitives
# ---------------------------------------------------------------------------

def test_quantize_kv_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 3, 32)) * 3.0
    q, s = cache_lib.quantize_kv(x)
    assert q.dtype == jnp.int8
    assert s.shape == x.shape[:-1]
    assert np.all(np.asarray(s) > 0)
    # per-element dequant error <= half a quantization step of that row
    err = np.abs(np.asarray(cache_lib.dequantize_kv(q, s)) - np.asarray(x))
    step = np.asarray(s)[..., None]
    assert np.all(err <= 0.5 * step + 1e-7)


def test_quantize_kv_zero_rows_are_exact():
    x = jnp.zeros((2, 5, 1, 16))
    q, s = cache_lib.quantize_kv(x)
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(s), 1.0)  # guarded, not 0/0
    np.testing.assert_array_equal(
        np.asarray(cache_lib.dequantize_kv(q, s)), 0.0)


def test_fake_quantize_is_idempotent():
    """dequant(quant(.)) is a projection: applying it twice is the
    identity on its image — the property that makes prefill's fake-quant
    attention and decode's stored-pool attention see the SAME values."""
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 9, 2, 64))
    f1 = cache_lib.fake_quantize_kv(x)
    f2 = cache_lib.fake_quantize_kv(f1)
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))


def test_kv_block_bytes_and_equal_byte_blocks():
    cfg = get_config("smollm-135m").reduced()
    bs = 16
    fp = cache_lib.kv_block_bytes(cfg, bs, "none")
    q8 = cache_lib.kv_block_bytes(cfg, bs, "int8")
    # int8 payload + fp32 scales still comes in under half the fp bytes
    assert 0 < q8 <= fp // 2
    n = cache_lib.equal_byte_blocks(cfg, 32, bs)
    assert n >= 64                       # equal bytes buy >= 2x the blocks
    assert n * q8 <= 32 * fp             # never over budget
    with pytest.raises(ValueError):
        cache_lib.kv_block_bytes(cfg, bs, "int4")


# ---------------------------------------------------------------------------
# Quantized pool struct + write/gather
# ---------------------------------------------------------------------------

def test_quant_paged_cache_struct_shapes_and_guards():
    cfg = get_config("smollm-135m").reduced()
    c = cache_lib.paged_cache_struct(cfg, batch=3, max_len=64, num_blocks=8,
                                     block_size=16, kv_quant="int8")
    assert c["k"].dtype == jnp.int8 and c["v"].dtype == jnp.int8
    kv = cache_lib.eff_kv_heads(cfg)
    assert c["k_scale"].shape == (cfg.num_layers, 8, 16, kv)
    assert c["k_scale"].dtype == jnp.float32
    assert cache_lib.is_quantized(c)
    fp = cache_lib.paged_cache_struct(cfg, 3, 64, 8, 16)
    assert not cache_lib.is_quantized(fp)
    with pytest.raises(ValueError):
        cache_lib.paged_cache_struct(cfg, 3, 64, 8, 16, kv_quant="int4")
    hy = get_config("recurrentgemma-2b").reduced()
    assert not cache_lib.supports_kv_quant(hy)
    with pytest.raises(ValueError):
        cache_lib.paged_cache_struct(hy, 3, 64, 8, 16, kv_quant="int8")


def test_quant_write_gather_roundtrip_is_fake_quantize():
    rng = np.random.RandomState(3)
    b, t, kv, d, bs, maxb, n = 2, 5, 2, 8, 4, 4, 10
    w = maxb * bs
    positions = jnp.asarray(rng.randint(0, w - t, size=(b, 1))
                            + np.arange(t)[None])
    k_new = jnp.asarray(rng.randn(b, t, kv, d), jnp.float32)
    v_new = jnp.asarray(rng.randn(b, t, kv, d), jnp.float32)
    perm = rng.permutation(n)
    table = jnp.asarray(np.stack([perm[:maxb], perm[maxb:2 * maxb]]))
    pk = jnp.zeros((n, bs, kv, d), jnp.int8)
    pv = jnp.zeros((n, bs, kv, d), jnp.int8)
    ks = jnp.zeros((n, bs, kv)); vs = jnp.zeros((n, bs, kv))
    pk, pv, ks, vs = cache_lib.write_kv_paged_quant(
        pk, pv, ks, vs, k_new, v_new, positions, table)
    gk, gv = cache_lib.gather_paged_kv_quant(pk, pv, ks, vs, table)
    # the gathered view is exactly the fake-quantized write, slot by slot
    fk = cache_lib.fake_quantize_kv(k_new)
    fv = cache_lib.fake_quantize_kv(v_new)
    for i in range(b):
        for j in range(t):
            p = int(positions[i, j])
            np.testing.assert_array_equal(np.asarray(gk[i, p]),
                                          np.asarray(fk[i, j]))
            np.testing.assert_array_equal(np.asarray(gv[i, p]),
                                          np.asarray(fv[i, j]))


def test_quant_write_respects_keep_mask_and_unallocated():
    b, t, kv, d, bs, maxb, n = 1, 4, 1, 4, 4, 3, 4
    table = jnp.asarray([[2, -1, -1]])
    positions = jnp.asarray([[2, 3, 4, 5]])      # 4,5 hit unalloc block
    keep = jnp.asarray([[True, False, True, True]])
    k_new = jnp.ones((b, t, kv, d)); v_new = jnp.ones((b, t, kv, d))
    pk = jnp.zeros((n, bs, kv, d), jnp.int8)
    pv = jnp.zeros((n, bs, kv, d), jnp.int8)
    ks = jnp.zeros((n, bs, kv)); vs = jnp.zeros((n, bs, kv))
    pk, pv, ks, vs = cache_lib.write_kv_paged_quant(
        pk, pv, ks, vs, k_new, v_new, positions, table, keep=keep)
    # only (block 2, offset 2) written: quantized ones at scale 1/127
    got = np.asarray(pk)
    assert got[2, 2].sum() == 127 * kv * d
    assert got.sum() == 127 * kv * d
    assert np.asarray(ks)[2, 2] == pytest.approx(1.0 / 127.0)
    assert float(np.asarray(ks).sum()) == pytest.approx(1.0 / 127.0)


def test_copy_scales_mirrors_copy_blocks():
    n, bs, kv = 6, 4, 2
    ks = jnp.arange(n * bs * kv, dtype=jnp.float32).reshape(1, n, bs, kv)
    vs = ks * 10.0
    src = jnp.asarray([1, n])        # second pair is the no-copy sentinel
    dst = jnp.asarray([4, n])
    ks2, vs2 = cache_lib.copy_scales(ks, vs, src, dst)
    np.testing.assert_array_equal(np.asarray(ks2[0, 4]),
                                  np.asarray(ks[0, 1]))
    np.testing.assert_array_equal(np.asarray(vs2[0, 4]),
                                  np.asarray(vs[0, 1]))
    # everything but the destination is untouched (sentinel dropped)
    keep = [i for i in range(n) if i != 4]
    np.testing.assert_array_equal(np.asarray(ks2[0, keep]),
                                  np.asarray(ks[0, keep]))


# ---------------------------------------------------------------------------
# Pallas kernel paged_ragged_verify_attention_quant vs oracle
# ---------------------------------------------------------------------------

QUANT_SHAPES = [
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


def _quant_attn_inputs(b, t, h, kv, d, n, bs, maxb, seed=0, layout="ragged"):
    """``paged_attn_inputs`` (tables, positions and queries by ``layout``)
    and the int8 pools of its fp pools."""
    q, pool_k, pool_v, table, qpos, kvp = paged_attn_inputs(
        b, t, h, kv, d, n, bs, maxb, seed=seed, layout=layout)
    qk, sk = cache_lib.quantize_kv(pool_k)
    qv, sv = cache_lib.quantize_kv(pool_v)
    return q, pool_k, pool_v, qk, qv, sk, sv, table, qpos, kvp


@pytest.mark.parametrize("shape", QUANT_SHAPES)
def test_quant_paged_kernel_vs_oracle(shape):
    """The JX006 parity contract for the quantized kernel: interpret-mode
    ``paged_ragged_verify_attention_quant`` against the pure-jnp oracle
    over ragged scrambled tables, GQA, windows, and live extents far
    below, not a multiple of, or past the table's width."""
    b, t, h, kv, d, n, bs, maxb, window, layout = (*shape, "ragged")[:10]
    (q, _, _, qk, qv, sk, sv, table, qpos,
     kvp) = _quant_attn_inputs(b, t, h, kv, d, n, bs, maxb, seed=b * 10 + t,
                               layout=layout)
    out = paged_ragged_verify_attention_quant(q, qk, qv, sk, sv, table,
                                              qpos, kvp, window=window,
                                              interpret=True)
    want = ref.paged_ragged_verify_attention_quant_ref(
        q, qk, qv, sk, sv, table, qpos, kvp, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_quant_kernel_bounded_error_vs_fp_pipeline():
    """int8 per-head amax quantization keeps the attention output close
    to the fp paged kernel on the same underlying values — the bound the
    serving-level divergence argument (DESIGN.md §13) leans on."""
    b, t, h, kv, d, n, bs, maxb = 2, 4, 8, 2, 64, 12, 16, 4
    (q, pk, pv, qk, qv, sk, sv, table, qpos,
     kvp) = _quant_attn_inputs(b, t, h, kv, d, n, bs, maxb, seed=5)
    fp = ref.paged_ragged_verify_attention_ref(q, pk, pv, table, qpos, kvp)
    qz = paged_ragged_verify_attention_quant(q, qk, qv, sk, sv, table,
                                             qpos, kvp, interpret=True)
    err = np.max(np.abs(np.asarray(fp) - np.asarray(qz)))
    assert err < 0.05, err


def test_ops_dispatch_quant_kernel_matches_ref():
    b, t, h, kv, d, n, bs, maxb = 2, 3, 4, 2, 32, 8, 8, 4
    (q, _, _, qk, qv, sk, sv, table, qpos,
     kvp) = _quant_attn_inputs(b, t, h, kv, d, n, bs, maxb, seed=9)
    via_kernel = ops.paged_ragged_attention_quant(
        q, qk, qv, sk, sv, table, qpos, kvp, force_kernel=True)
    via_ref = ops.paged_ragged_attention_quant(
        q, qk, qv, sk, sv, table, qpos, kvp)
    np.testing.assert_allclose(np.asarray(via_kernel), np.asarray(via_ref),
                               atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# Byte accounting at the admission boundary
# ---------------------------------------------------------------------------

def test_equal_byte_pool_admits_what_fp_pool_rejects():
    """The capacity story, as an admission boundary: at EQUAL BYTES the
    int8 pool holds >= 2x the blocks, so a request whose worst-case
    residency overflows the fp pool fits the quantized one."""
    cfg = get_config("smollm-135m").reduced()
    bs, fp_blocks = 16, 8
    q8_blocks = cache_lib.equal_byte_blocks(cfg, fp_blocks, bs)
    assert q8_blocks >= 2 * fp_blocks
    spec = SpecDecodeConfig(policy="static", static_sl=3)

    def sched(nblocks, kv_quant):
        sv = ServingConfig(max_batch_size=1, max_seq_len=256, paged_kv=True,
                           kv_block_size=bs, num_kv_blocks=nblocks,
                           prefix_caching=True, kv_quant=kv_quant)
        bb = cache_lib.kv_block_bytes(cfg, bs, kv_quant)
        return LookaheadScheduler(sv, spec, kv_mirror=True,
                                  block_bytes=bb)

    s_fp = sched(fp_blocks, "none")
    s_q8 = sched(q8_blocks, "int8")
    # same byte budget, >= 2x the block budget
    assert s_q8.kv_bytes_total() <= s_fp.kv_bytes_total()
    assert s_q8.kv_blocks_total() >= 2 * s_fp.kv_blocks_total()
    # a mid-size request: needs more blocks than the fp pool has, fewer
    # than the equal-byte int8 pool
    need_tokens = (fp_blocks * bs + bs)
    req = Request("r", prompt=list(range(need_tokens)), max_new_tokens=8)
    assert not s_fp._fits(req)
    assert s_q8._fits(req)


def test_engine_rejects_invalid_kv_quant_combinations():
    cfg = get_config("smollm-135m").reduced()
    pt = init_params(model_specs(cfg), jax.random.PRNGKey(1), jnp.float32)
    spec = SpecDecodeConfig(policy="static", drafter="ngram")
    with pytest.raises(ValueError, match="paged_kv"):
        ServingEngine(pt, cfg, None, None, spec,
                      ServingConfig(max_batch_size=1, max_seq_len=64,
                                    kv_quant="int8"))
    hy = get_config("recurrentgemma-2b").reduced()
    ph = init_params(model_specs(hy), jax.random.PRNGKey(1), jnp.float32)
    with pytest.raises(ValueError, match="quantized"):
        ServingEngine(ph, hy, None, None, spec,
                      ServingConfig(max_batch_size=1, max_seq_len=64,
                                    paged_kv=True, kv_block_size=16,
                                    kv_quant="int8"))
    with pytest.raises(ValueError, match="paged"):
        sd.init_round_state(cfg, None, spec, 1, 64, jax.random.PRNGKey(0),
                            kv_quant="int8")


# ---------------------------------------------------------------------------
# Serving engine over the quantized pool
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_pair():
    cfg = get_config("smollm-135m").reduced()
    pt = init_params(model_specs(cfg), jax.random.PRNGKey(1), jnp.float32)
    noise = init_params(model_specs(cfg), jax.random.PRNGKey(7), jnp.float32)
    pd = jax.tree_util.tree_map(lambda a, b: a + 0.05 * b, pt, noise)
    return cfg, pt, pd


def _run_quant_engine(cfg, pt, pd, drafter, kv_quant, *, policy="static",
                      max_new=12, seed=0):
    spec = SpecDecodeConfig(policy=policy, temperature=0.0, drafter=drafter)
    sv = ServingConfig(max_batch_size=2, max_seq_len=128, paged_kv=True,
                       kv_block_size=16, kv_quant=kv_quant)
    model = drafter == "model"
    eng = ServingEngine(pt, cfg, pd if model else None,
                        cfg if model else None, spec, sv, seed=seed)
    reqs = [Request(str(i), prompt=list(range(2 + i, 12 + i)),
                    max_new_tokens=max_new) for i in range(3)]
    m = eng.run(reqs)
    return [r.output for r in reqs], m


@pytest.mark.parametrize("drafter", ["model", "ngram"])
def test_quant_engine_completes_and_halves_bytes(small_pair, drafter):
    cfg, pt, pd = small_pair
    outs_fp, m_fp = _run_quant_engine(cfg, pt, pd, drafter, "none")
    outs_q8, m_q8 = _run_quant_engine(cfg, pt, pd, drafter, "int8")
    assert m_q8["requests_finished"] == 3
    assert all(len(o) == 12 for o in outs_q8)
    assert m_q8["kv_quant"] == "int8"
    # the headline: same block count, under half the bytes
    assert m_q8["kv_pool_blocks"] == m_fp["kv_pool_blocks"]
    assert m_q8["kv_block_bytes"] <= 0.5 * m_fp["kv_block_bytes"]
    assert m_q8["kv_pool_bytes"] <= 0.5 * m_fp["kv_pool_bytes"]


def test_quant_engine_deterministic_across_schedules(small_pair):
    """The quantized plane keeps the engine's schedule-invariance: the
    same requests produce identical greedy streams sync vs pipelined."""
    cfg, pt, pd = small_pair
    streams = {}
    for pipelined in (False, True):
        spec = SpecDecodeConfig(policy="static", temperature=0.0,
                                drafter="model")
        sv = ServingConfig(max_batch_size=2, max_seq_len=128, paged_kv=True,
                           kv_block_size=16, kv_quant="int8",
                           pipelined=pipelined)
        eng = ServingEngine(pt, cfg, pd, cfg, spec, sv, seed=0)
        reqs = [Request(str(i), prompt=list(range(2 + i, 12 + i)),
                        max_new_tokens=10) for i in range(3)]
        eng.run(reqs)
        streams[pipelined] = [r.output for r in reqs]
    assert streams[False] == streams[True]


# ---------------------------------------------------------------------------
# Serving-level statistical exactness over the quantized pool
# ---------------------------------------------------------------------------

def _tiny_cfg(vocab: int = 8) -> ModelConfig:
    return ModelConfig(name="stat-tiny-q", family="dense", num_layers=2,
                       d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
                       vocab_size=vocab, head_dim=16)


def _sharpened_params(cfg):
    pt = dict(init_params(model_specs(cfg), jax.random.PRNGKey(5),
                          jnp.float32))
    pt["embed"] = pt["embed"] * 5.0
    return pt


def _exact_two_token_dist_quant(pt, cfg, prompt, bs=16):
    """Ground-truth joint P(t1, t2 | prompt) under target-only sampling
    THROUGH the quantized paged cache — the reference the quantized
    engine must match exactly.  Computed with the same prefill program
    the engine runs: row 0 prefills the bare prompt (p1 from its last
    logits), rows 1..V prefill ``prompt + [t1]`` (p2 from theirs); the
    fake-quant prefill attention makes these bit-identical to the
    serving decode path over the stored int8 pool (DESIGN.md §13)."""
    v = cfg.vocab_size
    rows = 1 + v
    big = len(prompt) + 1
    maxb = -(-big // bs)
    n = rows * maxb
    c = cache_lib.paged_cache_struct(cfg, rows, maxb * bs, n, bs,
                                     require_full_seq=False,
                                     kv_quant="int8")
    table = jnp.arange(n, dtype=jnp.int32).reshape(rows, maxb)
    toks = np.zeros((rows, big), np.int32)
    lens = np.zeros((rows,), np.int32)
    toks[0, :len(prompt)] = prompt
    lens[0] = len(prompt)
    for t1 in range(v):
        toks[1 + t1] = prompt + [t1]
        lens[1 + t1] = big
    _, last = prefill_lib.prefill_paged_rows(
        pt, cfg, c["k"], c["v"], c["kv_pos"], table, jnp.asarray(toks),
        jnp.asarray(lens), k_scale=c["k_scale"], v_scale=c["v_scale"])
    p1 = np.asarray(jax.nn.softmax(last[0, :v]))
    joint = np.zeros((v, v))
    for t1 in range(v):
        p2 = np.asarray(jax.nn.softmax(last[1 + t1, :v]))
        joint[t1] = p1[t1] * p2
    return joint / joint.sum()


def _chi2(counts: np.ndarray, probs: np.ndarray, n: int):
    exp = probs.reshape(-1) * n
    obs = counts.reshape(-1)
    big = exp >= 5.0
    chi = float((((obs[big] - exp[big]) ** 2) / exp[big]).sum())
    if (~big).any():
        eo, ee = obs[~big].sum(), exp[~big].sum()
        if ee > 0:
            chi += float((eo - ee) ** 2 / ee)
    df = int(big.sum()) + (1 if (~big).any() else 0) - 1
    return chi, df


@pytest.mark.parametrize("drafter", ["model", "ngram"])
def test_quant_serving_stochastic_path_statistically_exact(drafter):
    """Chi-square serving exactness with ``kv_quant=int8``: the engine's
    temperature-1.0 two-token joint over the quantized pool matches the
    quantized-cache analytic reference (NOT the fp one — storage
    quantization shifts the target distribution, and exact rejection
    sampling must track the shifted target, bit for bit)."""
    cfg = _tiny_cfg(vocab=8)
    pt = _sharpened_params(cfg)
    noise = init_params(model_specs(cfg), jax.random.PRNGKey(6), jnp.float32)
    pd = jax.tree_util.tree_map(lambda a, b: a + 0.1 * b, pt, noise)
    prompt = [1, 2, 3, 1, 2, 3, 1, 2]
    joint = _exact_two_token_dist_quant(pt, cfg, prompt)

    n = 2400
    spec = SpecDecodeConfig(policy="static", static_sl=3, temperature=1.0,
                            drafter=drafter)
    model_free = drafter != "model"
    eng = ServingEngine(pt, cfg, None if model_free else pd,
                        None if model_free else cfg, spec,
                        ServingConfig(max_batch_size=32, max_seq_len=64,
                                      paged_kv=True, kv_block_size=16,
                                      kv_quant="int8"),
                        seed=0)
    reqs = [Request(i, prompt=list(prompt), max_new_tokens=2)
            for i in range(n)]
    m = eng.run(reqs)
    assert m["requests_finished"] == n
    counts = np.zeros((8, 8))
    for r in reqs:
        assert len(r.output) == 2
        counts[r.output[0], r.output[1]] += 1
    chi, df = _chi2(counts, joint, n)
    crit = df + 5.0 * np.sqrt(2.0 * df)
    assert chi < crit, (drafter, chi, df, crit)
    # teeth: the counts must NOT fit the uniform reference
    chi_u, df_u = _chi2(counts, np.full((8, 8), 1.0 / 64.0), n)
    assert chi_u > df_u + 5.0 * np.sqrt(2.0 * df_u), "test has no teeth"
