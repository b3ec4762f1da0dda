"""The serving path's Pallas kernels compile for a TPU v5e at real widths.

Nothing here runs on a chip: each test compiles for a *described*
``v5e:2x2`` topology (the TPU compiler ships with libtpu) and checks that
the kernel survived as a Mosaic custom call.  Interpret-mode tests cannot
see what this catches: block shapes the TPU lowering refuses, and
kernel bodies Mosaic cannot lower.  The topology is described inside a
fixture only — never at import — so every xdist worker collects the same
tests and only the worker given this file loads libtpu.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import ops
from repro.kernels.kld_accept import fused_kld_accept
from repro.kernels.ngram_match import ngram_suffix_propose
from repro.kernels.ragged_attention import (
    paged_ragged_verify_attention, paged_ragged_verify_attention_quant,
    ragged_verify_attention)

# smollm-135m at published widths, served at batch 16 x 2048 tokens
B, H, KV, D, BS, SEQ, V = 16, 9, 3, 64, 16, 2048, 49152
MAXB, N = SEQ // BS, 16 * SEQ // BS // 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu / no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable is written to the persistent cache but
    # cannot be read back without a chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (K, rows, pool blocks): batch 16 at K 1 and 10, and the offline
# benchmark cell's verify call (batch 128, K 4, a 4096-block pool)
PAGED_CALLS = dict(argnames="k, b, n",
                   argvalues=[(1, B, N), (10, B, N), (4, 128, 4096)],
                   ids=["1", "10", "offline"])


@pytest.mark.parametrize(**PAGED_CALLS)
def test_paged_verify_fp_compiles(one_chip, k, b, n):
    s = functools.partial(_sds, one_chip)
    txt = _compiled_text(
        paged_ragged_verify_attention,
        s((b, k + 1, H, D), jnp.float32), s((n, BS, KV, D), jnp.float32),
        s((n, BS, KV, D), jnp.float32), s((b, MAXB), jnp.int32),
        s((b, k + 1), jnp.int32), s((n, BS), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize(**PAGED_CALLS)
def test_paged_verify_int8_compiles(one_chip, k, b, n):
    s = functools.partial(_sds, one_chip)
    txt = _compiled_text(
        paged_ragged_verify_attention_quant,
        s((b, k + 1, H, D), jnp.float32), s((n, BS, KV, D), jnp.int8),
        s((n, BS, KV, D), jnp.int8), s((n, BS, KV), jnp.float32),
        s((n, BS, KV), jnp.float32), s((b, MAXB), jnp.int32),
        s((b, k + 1), jnp.int32), s((n, BS), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("k", [1, 10])
def test_ngram_propose_compiles(one_chip, k):
    s = functools.partial(_sds, one_chip)
    txt = _compiled_text(
        functools.partial(ngram_suffix_propose, n=3, k=k),
        s((B, SEQ), jnp.int32), s((B,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_dense_ring_verify_compiles(one_chip):
    s = functools.partial(_sds, one_chip)
    txt = _compiled_text(
        ragged_verify_attention,
        s((B, 11, H, D), jnp.float32), s((B, SEQ, KV, D), jnp.float32),
        s((B, SEQ, KV, D), jnp.float32), s((B, 11), jnp.int32),
        s((B, SEQ), jnp.int32))
    assert "tpu_custom_call" in txt


def test_fused_kld_compiles(one_chip):
    s = functools.partial(_sds, one_chip)
    txt = _compiled_text(
        fused_kld_accept, s((B, 10, V), jnp.float32),
        s((B, 10, V), jnp.float32), s((B, 10), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("shape", [(1, 4), (2, 2)])
@pytest.mark.parametrize("quant", [False, True])
def test_paged_verify_per_kv_head_shard_compiles(topo, monkeypatch, quant,
                                                 shape):
    """granite-8b's head layout (32 heads over 8 KV heads, head_dim 128)
    on a (data, model) mesh of four chips: on the serving plan's specs
    each chip runs the kernel on its own rows and KV heads (GSPMD would
    refuse to partition the bare Pallas call)."""
    from repro.launch.sharding import (ServeMeshPlan, kv_head_axis,
                                       serve_rules)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(shape), ("data", "model"))
    b, t, h, kv, d, n = 8, 5, 32, 8, 128, 8 * 128 // 2
    plan = ServeMeshPlan(mesh=mesh, rules=serve_rules(mesh, b))
    pool = jnp.int8 if quant else jnp.float32
    # the pool's own layout (serve_cache_shardings' rule for KV heads)
    pool_sh = NamedSharding(mesh, P(None, None,
                                    kv_head_axis(kv, mesh, plan.rules)))
    rep = NamedSharding(mesh, P())
    pools = [_sds(pool_sh, (n, BS, kv, d), pool)] * 2
    if quant:
        pools += [_sds(pool_sh, (n, BS, kv), jnp.float32)] * 2
    ctl = [_sds(rep, (b, MAXB), jnp.int32), _sds(rep, (b, t), jnp.int32),
           _sds(rep, (n, BS), jnp.int32)]
    attn = ops.paged_ragged_attention_quant if quant else \
        ops.paged_ragged_attention

    def body(q, *rest):
        with ops.sharded_kernels(plan):
            return attn(q, *rest)

    q = _sds(rep, (b, t, h, d), jnp.float32)
    compiled = jax.jit(body).lower(q, *pools, *ctl).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # each chip holds its share of the pool and never gathers the rest
    # (a gather would add at least half the pool)
    whole = 2 * n * BS * kv * d * jnp.dtype(pool).itemsize
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            < whole / shape[1] + whole / 8)
