"""``chip_smoke.py``'s phases at ``.reduced()`` widths on the CPU (kernels
in interpret mode, the model on its XLA path), the script's refusal to
run without a TPU, the per-shard kernel dispatch under a mesh, and the
compile-cache placement helper."""
import importlib
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh_from_shape

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return get_config("smollm-135m").reduced()


def test_refuses_to_run_without_a_tpu(smoke, capsys, monkeypatch):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert smoke.main() != 0
    assert capsys.readouterr().out == ""      # no result line


def test_kernel_phase(smoke, cfg):
    out = smoke.kernel_phase(cfg, batch=2, table_cols=4, seq_len=64,
                             interpret=True)
    assert out["cases"] == 9
    assert out["max_abs_err_fp"] < smoke.ATTN_ATOL
    assert out["max_abs_err_int8"] < smoke.ATTN_ATOL


def test_serving_phase(smoke, cfg):
    out = smoke.serving_phase(cfg, full=False, requests=3, max_new=8)
    assert out == {"runs": 5, "int8_near_ties": 0}


def test_http_phase(smoke, cfg):
    out = smoke.http_phase(cfg, full=False)
    assert out["tokens"] == 8 and out["finish_reason"] == "length"


def test_four_chip_phase_control_flow(smoke):
    """The mesh phase's control flow (sharded init, state born sharded,
    ambient-mesh round) on a 1x1 mesh of the one CPU device."""
    mesh = make_mesh_from_shape((1, 1), ("data", "model"))
    out = smoke.four_chip_phase(get_config("granite-8b").reduced(), mesh,
                                batch=2, requests=2, max_new=6)
    assert [set(m) for m in out["device_memory"]] in (
        [], [{"bytes_in_use", "peak_bytes_in_use", "bytes_limit"}])


def test_serving_phase_catches_a_diverging_stream(smoke):
    logits = np.array([0.0, 0.0, 0.0, 1.0, 9.0])
    found = smoke.divergences([[0]], [[1, 2, 3]], [[1, 2, 4]],
                              lambda toks: logits)
    with pytest.raises(RuntimeError, match="leaves the autoregressive"):
        smoke.check_streams("x", found, smoke.TIE_TOL)


def test_serving_phase_admits_a_near_tie(smoke):
    """A divergence where both tokens sit at the top logit is counted,
    not raised, under the int8 pool's tolerance; the logits are taken
    after the shared prefix."""
    seen = []

    def next_logits(toks):
        seen.append(toks)
        return np.array([0.0, 0.0, 0.0, 9.0, 9.0])

    found = smoke.divergences([[0], [0]], [[1, 2, 3], [5]],
                              [[1, 2, 4], [5]], next_logits)
    assert seen == [[0, 1, 2]]
    assert found == [{"request": 0, "diverged_at": 2, "tokens": [3, 4],
                      "below_top_logit": 0.0,
                      "logit_std": pytest.approx(np.std([0, 0, 0, 9, 9]))}]
    assert smoke.check_streams("x", found, smoke.TIE_TOL) == 1


def test_strict_check_refuses_even_an_exact_tie(smoke):
    """The fp pool and the mesh are held to token-for-token equality."""
    found = smoke.divergences([[0]], [[3]], [[4]],
                              lambda toks: np.array([0.0, 0, 0, 9, 9]))
    with pytest.raises(RuntimeError, match="limit 0.0"):
        smoke.check_streams("x", found)
    assert smoke.check_streams("x", []) == 0


@pytest.mark.parametrize("kv_quant,atol", [("none", 1e-4), ("int8", 5e-2)])
def test_next_logits_match_the_reference_forward(smoke, cfg, kv_quant, atol):
    """The near-tie check's logits are the plain forward's last-position
    logits over the same tokens: to summation order on the fp pool, to
    the storage error (DESIGN.md §13) on the int8 pool."""
    from repro.models.module import init_params
    from repro.models.transformer import forward, model_specs
    params = init_params(model_specs(cfg), jax.random.PRNGKey(1),
                         jnp.float32)
    toks = list(range(3, 24))
    got = smoke._next_logits(cfg, params, kv_quant)(toks)
    want = forward(params, cfg, jnp.asarray([toks], jnp.int32),
                   mode="train")[0][0, -1, :cfg.vocab_size]
    np.testing.assert_allclose(got, np.asarray(want), atol=atol, rtol=1e-4)


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
@pytest.mark.parametrize("quant", [False, True])
def test_paged_dispatch_under_mesh_matches_oracle(monkeypatch, quant,
                                                  shape):
    """On a serving plan's specs the dispatcher runs the (interpret-mode)
    kernel per shard inside a shard_map — on 2x2, rows over data and KV
    heads over model — and still matches the oracle.  Without four
    devices the 2x2 case reruns itself in a child with four forced host
    devices."""
    from repro.launch.sharding import ServeMeshPlan, serve_rules
    if len(jax.devices()) < shape[0] * shape[1]:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.pathsep.join(
                       [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
        node = (f"{Path(__file__).resolve()}::"
                f"test_paged_dispatch_under_mesh_matches_oracle"
                f"[{quant}-shape1]")
        run = subprocess.run([sys.executable, "-m", "pytest", "-q",
                              "-p", "no:cacheprovider", node], env=env,
                             cwd=ROOT, capture_output=True, text=True,
                             timeout=300)
        assert run.returncode == 0 and "1 passed" in run.stdout, \
            run.stdout[-3000:] + run.stderr[-3000:]
        return
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    rng = np.random.RandomState(3)
    b, t, h, kv, d, n, bs, maxb = 2, 3, 4, 2, 32, 8, 8, 4
    perm = rng.permutation(n)[:maxb]
    table = jnp.asarray(np.stack([perm] * b), jnp.int32)
    qpos = jnp.asarray(np.tile(np.arange(20, 20 + t), (b, 1)), jnp.int32)
    # position p sits in logical block p // bs at slot p % bs, as the
    # pool's writes leave it; a fifth of the slots are empty
    pos = np.full((n, bs), -1)
    pos[perm] = np.arange(maxb * bs).reshape(maxb, bs)
    kvp = jnp.asarray(np.where(rng.rand(n, bs) < 0.8, pos, -1), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    if quant:
        pools = [jnp.asarray(rng.randint(-127, 128, (n, bs, kv, d)), jnp.int8)
                 for _ in range(2)]
        pools += [jnp.asarray(rng.uniform(1e-3, 2e-2, (n, bs, kv)),
                              jnp.float32) for _ in range(2)]
        attn, oracle = (ops.paged_ragged_attention_quant,
                        ref.paged_ragged_verify_attention_quant_ref)
    else:
        pools = [jnp.asarray(rng.standard_normal((n, bs, kv, d)), jnp.float32)
                 for _ in range(2)]
        attn, oracle = (ops.paged_ragged_attention,
                        ref.paged_ragged_verify_attention_ref)
    mesh = make_mesh_from_shape(shape, ("data", "model"))
    plan = ServeMeshPlan(mesh=mesh, rules=serve_rules(mesh, b))
    in_specs, out_spec = plan.paged_attention_specs(b, kv, quant=quant)
    if shape == (2, 2):     # the plan splits both rows and KV heads
        assert out_spec == P("data", None, "model")

    def body(*args):
        with ops.sharded_kernels(plan):
            return attn(*args, force_kernel=True, interpret=True)

    got = jax.jit(body)(  # speclint: disable=JX004 (test-local program)
        q, *pools, table, qpos, kvp)
    want = oracle(q, *pools, table, qpos, kvp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_an_outside_directory_alone(monkeypatch,
                                                         tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_importing_sets_no_cache():
    before = jax.config.jax_compilation_cache_dir
    importlib.reload(compile_cache)
    assert jax.config.jax_compilation_cache_dir == before
