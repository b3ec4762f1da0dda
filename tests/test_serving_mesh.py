"""Serving under a (data, model) mesh (DESIGN.md §5).

The contract this file enforces is the one PRs 2–4 established for the
paged layout and the pipelined schedule, extended across DEVICE LAYOUTS:
greedy token streams must be **byte-identical** between the single-device
engine and a meshed engine — for every registered policy × drafter, both
KV layouts, both schedules, and under forced preemption.  (Greedy
speculative decoding is exact, so the only way a mesh could change a
token is a real data-plane bug: a mis-sharded cache write, a clipped
gather, a drifted RNG key.)

The identity runs need forced host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``, the CI
``multidevice`` lane); without them those tests skip and only the pure
rule-table unit tests run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import ServingConfig, SpecDecodeConfig
from repro.core.drafters import available_drafters
from repro.core.policies import available_policies
from repro.launch.mesh import make_mesh_from_shape, serving_mesh
from repro.launch.sharding import (kv_head_axis, serve_cache_shardings,
                                   serve_rules)
from repro.models.module import init_params
from repro.models.transformer import forward, model_specs
from repro.serving.engine import ServingEngine
from repro.serving.request import Request

jax.config.update("jax_platform_name", "cpu")

MULTI = len(jax.devices()) >= 4
requires_devices = pytest.mark.skipif(
    not MULTI, reason="needs XLA_FLAGS=--xla_force_host_platform_"
    "device_count=4 (the CI multidevice lane sets it)")

MESHES = ("1x4", "2x2")


@pytest.fixture(autouse=True)
def _drop_compiled_programs():
    """With four forced host devices, one process compiling the whole
    identity matrix crashed XLA's CPU compiler (JAX 0.9) about forty
    tests in; dropping each test's compiled programs lets it finish."""
    yield
    if MULTI:
        jax.clear_caches()
ALL_POLICIES = tuple(available_policies())
ALL_DRAFTERS = tuple(available_drafters())


# ---------------------------------------------------------------------------
# Rule-table units (run everywhere, no forced devices needed)
# ---------------------------------------------------------------------------

class _FakeMesh:
    axis_names = ("data", "model")

    class devices:
        shape = (2, 4)


def test_kv_head_axis_uneven_guard():
    """The 2-head miniatures must REPLICATE their KV head dim (vLLM's
    KV-head replication), not shard it unevenly; divisible counts
    shard."""
    rules = serve_rules(make_mesh_from_shape((1, 1), ("data", "model")), 8)
    assert kv_head_axis(2, _FakeMesh, rules) is None       # 2 % 4 != 0
    assert kv_head_axis(1, _FakeMesh, rules) is None
    assert kv_head_axis(8, _FakeMesh, rules) == "model"    # 8 % 4 == 0
    assert kv_head_axis(4, _FakeMesh, rules) == "model"


def test_serve_rules_table():
    mesh = make_mesh_from_shape((1, 1), ("data", "model"))
    rules = serve_rules(mesh, 8)
    assert rules.heads == "model" and rules.mlp == "model"
    assert rules.vocab == "model"
    assert rules.embed is None          # serving TP: no FSDP on weights
    assert rules.cache_seq is None      # KV heads shard instead (§5)
    assert rules.batch == ("data",)
    # odd batch over a (fake) 2-wide data axis must refuse to shard
    assert serve_rules(_FakeMesh, 7).batch == ()


def test_serve_cache_shardings_layout_contract():
    """Paged pools keep the block axis whole + tables replicate; dense
    rows shard batch over data; all control leaves replicate.  Specs are
    canonical (no trailing Nones) so round signatures never alternate
    between equal-but-unequal specs."""
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh_from_shape((1, 1), ("data", "model"))
    rules = serve_rules(mesh, 4)
    paged = {"k": jnp.zeros((2, 8, 4, 1, 8)), "v": jnp.zeros((2, 8, 4, 1, 8)),
             "kv_pos": jnp.zeros((8, 4), jnp.int32),
             "block_table": jnp.zeros((4, 8), jnp.int32),
             "length": jnp.zeros((4,), jnp.int32)}
    sh = serve_cache_shardings(paged, mesh, rules)
    assert sh["k"].spec[1] is None            # pool block axis stays whole
    assert sh["block_table"].spec == P()      # host rewrites rows piecemeal
    assert sh["kv_pos"].spec == P()
    dense = {"k": jnp.zeros((2, 4, 32, 1, 8)), "v": jnp.zeros((2, 4, 32, 1, 8)),
             "kv_pos": jnp.zeros((4, 32), jnp.int32),
             "length": jnp.zeros((4,), jnp.int32)}
    shd = serve_cache_shardings(dense, mesh, rules)
    assert shd["k"].spec[1] == "data"         # batch rows over data
    ngram = {"tokens": jnp.zeros((4, 64), jnp.int32),
             "length": jnp.zeros((4,), jnp.int32)}
    shn = serve_cache_shardings(ngram, mesh, rules)
    assert shn["tokens"].spec == P(("data",))
    assert shn["length"].spec == P()


# ---------------------------------------------------------------------------
# Meshed-engine identity (forced-device lane)
# ---------------------------------------------------------------------------

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


def _prompts(cfg, sizes=(7, 12, 5), seed=11):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, size=n).tolist() for n in sizes]


def _serve(cfg, pt, pd, *, policy="dsde", drafter="model", mesh=None,
           paged=False, pipelined=False, prompts=None, max_new=10,
           batch=2, max_seq=128, bs=16, nblocks=None):
    spec = SpecDecodeConfig(policy=policy, drafter=drafter, temperature=0.0)
    sv = ServingConfig(max_batch_size=batch, max_seq_len=max_seq,
                       paged_kv=paged, kv_block_size=bs,
                       num_kv_blocks=nblocks, pipelined=pipelined)
    from repro.core.drafters import build_drafter
    model_free = not build_drafter(spec, cfg, cfg).uses_draft_model()
    eng = ServingEngine(pt, cfg, None if model_free else pd,
                        None if model_free else cfg, spec, sv, seed=0,
                        mesh=serving_mesh(mesh) if mesh else None)
    reqs = [Request(i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    m = eng.run(reqs)
    return [r.output for r in reqs], m, eng


@pytest.fixture(scope="module")
def reference(small_pair):
    """Target-only greedy rollouts — what EVERY exact engine must emit,
    single-device or meshed, any policy/drafter/layout/schedule."""
    cfg, pt, _ = small_pair
    prompts = _prompts(cfg)
    return prompts, [greedy_rollout(pt, cfg, p, 10) for p in prompts]


@requires_devices
@pytest.mark.parametrize("pipelined", [False, True], ids=["sync", "pipe"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_single_device_engine_matches_rollout(small_pair, reference,
                                              paged, pipelined):
    """Anchor: the un-meshed engine reproduces the target rollout, so the
    meshed tests below compare against the same reference stream."""
    cfg, pt, pd = small_pair
    prompts, ref = reference
    out, m, _ = _serve(cfg, pt, pd, paged=paged, pipelined=pipelined,
                       prompts=prompts)
    assert out == ref
    assert m["requests_finished"] == len(prompts)


@requires_devices
@pytest.mark.parametrize("drafter", ALL_DRAFTERS)
@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_mesh_identity_policy_drafter_matrix(small_pair, reference,
                                             policy, drafter):
    """Every registered policy × drafter serves byte-identically to the
    single-device reference on a forced-host mesh, across dense + paged
    and sync + pipelined.  The mesh alternates 1x4 / 2x2 per (layout,
    schedule) cell so both shapes cover the full matrix without doubling
    the lane's runtime; the dsde×model cross below runs every cell on
    BOTH meshes."""
    cfg, pt, pd = small_pair
    prompts, ref = reference
    for i, (paged, pipelined) in enumerate(
            [(False, False), (False, True), (True, False), (True, True)]):
        mesh = MESHES[(ALL_POLICIES.index(policy)
                       + ALL_DRAFTERS.index(drafter) + i) % 2]
        out, m, eng = _serve(cfg, pt, pd, policy=policy, drafter=drafter,
                             mesh=mesh, paged=paged, pipelined=pipelined,
                             prompts=prompts)
        tag = (policy, drafter, mesh, paged, pipelined)
        assert m["requests_finished"] == len(prompts), tag
        assert out == ref, tag


@requires_devices
@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize("pipelined", [False, True], ids=["sync", "pipe"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_mesh_identity_full_cross_dsde_model(small_pair, reference, mesh,
                                             paged, pipelined):
    cfg, pt, pd = small_pair
    prompts, ref = reference
    out, m, _ = _serve(cfg, pt, pd, mesh=mesh, paged=paged,
                       pipelined=pipelined, prompts=prompts)
    assert out == ref, (mesh, paged, pipelined)
    assert m["requests_finished"] == len(prompts)


@requires_devices
@pytest.mark.parametrize("mesh", MESHES)
def test_mesh_exact_under_forced_preemption(small_pair, mesh):
    """Pool pressure on a meshed engine: eviction wipes the victim's
    replicated table row on every shard, recompute-on-readmit reprefills
    into resharded pools — the dense single-device stream must survive
    all of it."""
    cfg, pt, pd = small_pair
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in (30, 25, 20)]
    dense, _, _ = _serve(cfg, pt, pd, prompts=prompts, max_new=40, bs=8)
    out, m, _ = _serve(cfg, pt, pd, mesh=mesh, paged=True, pipelined=True,
                       prompts=prompts, max_new=40, bs=8, nblocks=16)
    assert m["preemptions"] >= 1
    assert m["requests_finished"] == 3
    assert dense == out


# ---------------------------------------------------------------------------
# Sharding-spec assertions + no-recompile guard (forced-device lane)
# ---------------------------------------------------------------------------

def _flat_axes(spec):
    out = []
    for part in tuple(spec):
        if part is None:
            continue
        out += list(part) if isinstance(part, tuple) else [part]
    return out


@requires_devices
def test_engine_places_params_and_state_on_mesh(small_pair):
    cfg, pt, pd = small_pair
    spec = SpecDecodeConfig(policy="static", drafter="model",
                            temperature=0.0)
    sv = ServingConfig(max_batch_size=4, max_seq_len=128, paged_kv=True,
                       kv_block_size=16)
    eng = ServingEngine(pt, cfg, pd, cfg, spec, sv,
                        mesh=serving_mesh("2x2"))
    # params: tensor-parallel over *model*, never over *data*
    axes = [a for leaf in jax.tree_util.tree_leaves(eng.pt)
            for a in _flat_axes(leaf.sharding.spec)]
    assert "model" in axes and "data" not in axes
    st = eng.state
    # paged pools: KV head dim under the uneven guard (1 head -> whole),
    # block axis never sharded, tables + control vectors replicated
    assert _flat_axes(st.target_cache["k"].sharding.spec) == []
    assert _flat_axes(st.target_cache["block_table"].sharding.spec) == []
    for leaf in (st.pending, st.done, st.tokens_budget, st.sl_next):
        assert _flat_axes(leaf.sharding.spec) == []
    # the draft mirror inherits the target pool's specs
    assert (st.draft_cache["k"].sharding.spec
            == st.target_cache["k"].sharding.spec)


@requires_devices
def test_ngram_token_buffer_data_sharded(small_pair):
    cfg, pt, _ = small_pair
    spec = SpecDecodeConfig(policy="static", drafter="ngram",
                            temperature=0.0)
    sv = ServingConfig(max_batch_size=4, max_seq_len=128)
    eng = ServingEngine(pt, cfg, None, None, spec, sv,
                        mesh=serving_mesh("2x2"))
    assert _flat_axes(eng.state.draft_cache["tokens"].sharding.spec) \
        == ["data"]
    # dense target rows: batch slots over data
    assert "data" in _flat_axes(eng.state.target_cache["k"].sharding.spec)


@requires_devices
def test_no_recompile_across_rounds_on_fixed_mesh(small_pair):
    """Consecutive rounds at a fixed bucket on a fixed mesh reuse ONE
    program: the engine's eager per-slot updates (admission scatters,
    block-table rewrites, shrink) must never drift an input layout into
    a fresh jit signature."""
    cfg, pt, pd = small_pair
    prompts = _prompts(cfg)
    _, _, eng = _serve(cfg, pt, pd, mesh="1x4", paged=True,
                       prompts=prompts, max_new=8)
    # round jits are shared ACROSS engines (equal config -> same program),
    # so earlier tests may already have populated entries for other cache
    # geometries; the guard is NO GROWTH while this engine keeps serving,
    # i.e. every later round re-hits the program its first round traced.
    sizes = {k: fn._cache_size() for k, fn in eng._mesh_round_fns.items()}
    assert sizes, "engine ran no meshed rounds"
    reqs = [Request(100 + i, prompt=p, max_new_tokens=8)
            for i, p in enumerate(_prompts(cfg, seed=29))]
    eng.run(reqs)
    after = {k: fn._cache_size() for k, fn in eng._mesh_round_fns.items()}
    for k, n in sizes.items():
        assert after[k] == n, (k, sizes, after)


@requires_devices
def test_round_state_shardings_cover_state(small_pair):
    """The declared in/out sharding tree matches the real RoundState
    structure leaf-for-leaf (a drifted tree would silently fall back to
    prefix broadcasting and lose the per-leaf layout contract)."""
    cfg, pt, pd = small_pair
    spec = SpecDecodeConfig(policy="dsde", drafter="model", temperature=0.0)
    sv = ServingConfig(max_batch_size=2, max_seq_len=128)
    eng = ServingEngine(pt, cfg, pd, cfg, spec, sv,
                        mesh=serving_mesh("1x4"))
    assert (jax.tree_util.tree_structure(eng._state_sh)
            == jax.tree_util.tree_structure(eng.state))
