"""The serving engine's own profiler spans and per-phase CPU counters.

A profiled ``pump()`` writes ``engine.*`` spans nested as the engine
docstring lists them, each pump a step ``engine.round`` numbered by the
round it dispatches; the round log names each round by the same ordinal
and books the thread CPU time of the plan, dispatch and collect that
served it.  Run at the benchmark's tiny CPU sizes (``bench/tests/tiny.py``).
"""
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, traces  # noqa: E402
from bench.tests import tiny  # noqa: E402

# the innermost engine span each span lies in
PARENTS = {
    "engine.plan": {"engine.round"},
    "engine.admit": {"engine.plan"},
    "engine.prefill": {"engine.admit"},
    "engine.prefill_draft": {"engine.prefill"},
    "engine.pick_bucket": {"engine.plan"},
    "engine.plan_blocks": {"engine.plan"},
    "engine.block_sync": {"engine.plan_blocks", "engine.collect",
                          "engine.prefill"},
    "engine.dispatch": {"engine.round"},
    "engine.round_call": {"engine.dispatch"},
    "engine.collect": {"engine.round"},
    "engine.collect_wait": {"engine.collect"},
    "engine.reconcile": {"engine.collect"},
    "engine.round": {None},
}


def _system(pipelined=True):
    over = tiny.overrides("smollm135m.offline")
    cfg = harness._merge(harness.load_config("smollm-135m"), over["config"])
    cfg["serving"] = dict(cfg["serving"], pipelined=pipelined)
    return harness.build(cfg, 3)


def _submit(eng, n, rng, prompt=20, max_new=24):
    for i in range(n):
        eng.submit(harness.make_request(
            i, rng.integers(0, 512, prompt).tolist(), max_new,
            time.monotonic()))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Four pipelined pumps from the first admission on, profiled."""
    system = _system()
    eng = system.engine
    _submit(eng, 3, np.random.default_rng(0))
    d = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        for _ in range(4):
            eng.pump()
    finally:
        jax.profiler.stop_trace()
    spans = [e for e in traces.load(str(d))
             if e["name"].startswith("engine.") and e["dur_ns"] > 0]
    return eng, spans


def _innermost_parent(span, spans):
    s, e = span["start_ns"], span["start_ns"] + span["dur_ns"]
    around = [h for h in spans if h is not span
              and h["line"] == span["line"] and h["plane"] == span["plane"]
              and h["start_ns"] <= s and e <= h["start_ns"] + h["dur_ns"]
              and h["dur_ns"] >= span["dur_ns"]]
    return min(around, key=lambda h: h["dur_ns"], default=None)


def test_profiled_pump_writes_every_engine_span(traced):
    _, spans = traced
    assert {e["name"] for e in spans} == set(PARENTS)


def test_engine_spans_nest_as_listed(traced):
    _, spans = traced
    for span in spans:
        parent = _innermost_parent(span, spans)
        assert (parent["name"] if parent else None) in PARENTS[
            span["name"]], span["name"]


def test_round_spans_carry_the_round_ordinal(traced):
    eng, spans = traced
    steps = sorted((e for e in spans if e["name"] == "engine.round"),
                   key=lambda e: e["start_ns"])
    assert [e["stats"]["step_num"] for e in steps] == [0, 1, 2, 3]
    k_of = {r["round"]: r["k"] for r in eng.round_log}
    for step in steps:
        inside = [e for e in spans if _innermost_parent(e, spans) is step]
        (disp,) = [e for e in inside if e["name"] == "engine.dispatch"]
        # a pump dispatches the round its step is numbered by and
        # collects the one before
        assert disp["stats"]["round"] == step["stats"]["step_num"]
        if disp["stats"]["round"] in k_of:
            assert disp["stats"]["k"] == k_of[disp["stats"]["round"]]
        for coll in (e for e in inside if e["name"] == "engine.collect"):
            assert coll["stats"]["round"] == step["stats"]["step_num"] - 1
    assert [r["round"] for r in eng.round_log] == [0, 1, 2]


def test_prefill_and_block_sync_spans_carry_their_sizes(traced):
    _, spans = traced
    (pre,) = [e for e in spans if e["name"] == "engine.prefill"]
    assert pre["stats"]["rows"] == 3 and pre["stats"]["bucket"] == 32
    assert str(pre["stats"]["warm"]) in ("False", "0")
    syncs = [e["stats"] for e in spans if e["name"] == "engine.block_sync"]
    assert syncs and all(s["rows"] >= 0 and s["fresh"] >= 0 for s in syncs)
    assert any(s["fresh"] > 0 for s in syncs)


def test_round_log_books_phase_cpu_seconds(traced):
    eng, _ = traced
    for r in eng.round_log:
        for key in ("plan_cpu_s", "dispatch_cpu_s", "collect_cpu_s"):
            assert r[key] >= 0.0, key
        assert "lookahead" not in r and "t_round_pred_s" not in r
    # the first round's plan prefilled its admissions
    assert eng.round_log[0]["plan_cpu_s"] > 0.0


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["sync", "pipelined"])
def test_phase_cpu_counters_within_the_pump_clock(pipelined):
    """The three counters of a round are the driving thread's CPU time
    inside the phases, so over a whole run they sum to at most the
    thread's CPU time around the pumps."""
    system = _system(pipelined)
    eng = system.engine
    _submit(eng, 3, np.random.default_rng(1), max_new=12)
    total = 0.0
    while eng.has_pending_work():
        t = time.thread_time()
        eng.pump()
        total += time.thread_time() - t
    t = time.thread_time()
    eng.drain()
    total += time.thread_time() - t
    booked = sum(r["plan_cpu_s"] + r["dispatch_cpu_s"] + r["collect_cpu_s"]
                 for r in eng.round_log)
    assert 0.0 < booked <= total
    assert [r["round"] for r in eng.round_log] == list(range(eng.rounds))


def test_model_drafter_rounds_book_their_draft_depth(tmp_path):
    """A model drafter sampled at T = 1 in the pipelined engine: every
    round is dispatched at ``sl_max``, and the round log books the depth
    the round's rows used (their most proposals, plus the step that
    writes the last draft token's keys), within the ``k + 1`` steps
    dispatched.  An admission runs the draft's prefill under its own
    span inside the group's ``engine.prefill``."""
    over = tiny.overrides("granite8b-l9.chat")
    cfg = harness._merge(harness.load_config("granite-8b-l9"),
                         over["config"])
    eng = harness.build(cfg, 5).engine
    assert eng.spec.temperature == 1.0 and eng.serving.pipelined
    depth = []
    log_round = eng._log_round

    def spy(rec, host, *a):
        depth.append(int(host.n_prop[host.live].max()) + 1
                     if rec.k > 0 and host.live.any() else 0)
        return log_round(rec, host, *a)
    eng._log_round = spy
    _submit(eng, 3, np.random.default_rng(2), max_new=16)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(2):
            eng.pump()
    finally:
        jax.profiler.stop_trace()
    harness.run_engine_until_idle(eng)
    assert len(eng.round_log) >= 3
    for r, want in zip(eng.round_log, depth, strict=True):
        assert r["k"] == eng.spec.sl_max
        assert r["draft_steps_effective"] == want
        # 0 for a round whose rows had all finished before it ran
        assert 0 <= r["draft_steps_effective"] <= r["k"] + 1
    assert min(depth[:2]) >= 1
    assert eng.draft_steps_effective == sum(depth)
    spans = [e for e in traces.load(str(tmp_path))
             if e["name"].startswith("engine.") and e["dur_ns"] > 0]
    (pre,) = [e for e in spans if e["name"] == "engine.prefill"]
    (draft,) = [e for e in spans if e["name"] == "engine.prefill_draft"]
    assert _innermost_parent(draft, spans) is pre
