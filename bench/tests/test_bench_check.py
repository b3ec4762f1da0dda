"""How ``correct`` is decided: the control (the reference one precision
step below the configuration's, in the program's place) must fail, and a
run with the timed path broken underneath must come out not correct.
Both at a size a test run holds; the chip readings behind the limits
are in PERF.md."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, harness  # noqa: E402
from bench.tests import tiny  # noqa: E402

CELLS = ["granite8b-l9.chat", "smollm135m.offline"]


@pytest.fixture
def fresh_programs():
    """Faults are planted in module globals that jitted programs read
    when traced: trace afresh before and after."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload, tmp_path):
    out = tiny.run(workload, seed=21, control=True,
                   root=tiny.bench_root(tmp_path))
    limits = out["check"]
    program = {k: v["value"] for k, v in limits.items()}
    control = out["_info"]["control"]
    lim = {k: v["limit"] for k, v in limits.items()}
    assert out["correct"] and check.within(program, lim)
    assert not check.within(control, lim), (program, control, lim)
    assert control["kv_err"] > 3 * program["kv_err"]


def _shift_tokens(real):
    def faulty(*a, **k):
        r = real(*a, **k)
        v = k["vocab_size"]
        bump = jnp.where(r.emitted < v, (r.emitted + 1) % v, r.emitted)
        return r._replace(emitted=bump, next_token=(r.next_token + 1) % v)
    return faulty


def _keep_cache(real):
    def faulty(params, cfg, tokens, snapshot, verified, n_committed):
        return snapshot
    return faulty


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault,target", [
    (_shift_tokens, "rejection_sample"),   # a token altered where produced
    (_keep_cache, "commit"),               # a round returns its state unchanged
])
def test_broken_timed_path_is_not_correct(workload, fault, target,
                                          monkeypatch, fresh_programs,
                                          tmp_path):
    from repro.core import spec_decode
    monkeypatch.setattr(spec_decode, target,
                        fault(getattr(spec_decode, target)))
    out = tiny.run(workload, seed=22, root=tiny.bench_root(tmp_path))
    assert not out["correct"], out["check"]


def test_reference_matches_its_own_control_to_rounding():
    """The two precisions of the reference differ by rounding only: the
    logits of a random model agree to 1e-3 of their spread."""
    cfg = harness._merge(harness.load_config("granite-8b-l9"), tiny.TARGET)
    ref = harness.reference_module(cfg)
    w = ref.init_weights(cfg, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, 512, 50).tolist()
    a, ka, va = ref.forward(w, cfg, toks, "f32")
    b, kb, vb = ref.forward(w, cfg, toks, "bf16x3")
    assert a.shape == (50, 512) and ka.shape == (2, 50, 2, 16)
    spread = float(jnp.std(a))
    assert float(jnp.max(jnp.abs(a - b))) < 1e-3 * spread
    assert float(jnp.max(jnp.abs(a - b))) > 0.0
