"""The attribution of a trace to the engine's spans and the round
program's stages (``bench/spans.py``)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, spans, traces  # noqa: E402
from bench.tests import tiny  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(line, name, start_us, dur_us, plane=DEV, **stats):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3,
            "stats": stats}


def op(start_us, dur_us, name="fusion.1"):
    return ev("XLA Ops", name, start_us, dur_us)


def host(name, start_us, dur_us):
    return ev("python3", name, start_us, dur_us, plane=HOST)


def nested():
    """Ops at 0-15, 45-100 and 150-160 us of a 200 us window; a plan
    holding a block sync, in a round step."""
    return [op(0, 15), op(45, 55), op(150, 10),
            host("engine.round", 0, 200), host("engine.plan", 10, 50),
            host("engine.block_sync", 20, 20)]


def unspanned():
    """Idle at 10-20 and 30-45 us outside any engine span; a bench span
    and a JAX call there do not count."""
    return [op(0, 10), op(45, 5), host("engine.plan", 20, 10),
            host("bench.plan", 12, 30), host("PjitFunction(x)", 32, 4)]


def us(idle):
    return {k: round(v * 1e6, 6) for k, v in idle.items()}


def test_idle_is_charged_to_the_innermost_engine_span():
    events = nested()
    lo, hi = traces.window_bounds(events)
    assert us(spans.idle_by_span(events, lo, hi)) == {
        "engine.plan": 10.0, "engine.block_sync": 20.0,
        "engine.round": 90.0}


def test_idle_with_no_engine_span():
    events = unspanned()
    lo, hi = traces.window_bounds(events)
    assert us(spans.idle_by_span(events, lo, hi)) == {
        "engine.plan": 10.0, spans.NO_SPAN: 25.0}


def test_a_gap_across_a_span_edge_is_split_at_the_edge():
    """Each instant counts where it lies, not the whole gap where its
    middle lies."""
    events = [op(0, 10), op(30, 10), host("engine.collect", 20, 40)]
    assert us(spans.idle_by_span(events, 0, 60e3)) == {
        spans.NO_SPAN: 10.0, "engine.collect": 30.0}


def test_many_short_gaps_all_count():
    events = [op(2 * i, 1) for i in range(100)]
    events.append(host("engine.block_sync", 0, 200))
    assert us(spans.idle_by_span(events, 0, 200e3)) == {
        "engine.block_sync": 100.0}


def test_idle_over_two_chips_is_where_neither_runs():
    events = [op(0, 20), dict(op(15, 20), plane="/device:TPU:1"),
              host("engine.dispatch", 0, 50)]
    assert us(spans.idle_by_span(events, 0, 50e3)) == {
        "engine.dispatch": 15.0}


RECORDED = {
    "old": ROOT / "bench/tests/data/smollm_offline_excerpt.json",
    "engine": ROOT / "bench/tests/data/smollm_offline_engine_spans.json",
}


@pytest.mark.parametrize("case", ["nested", "unspanned", "old", "engine"])
def test_idle_by_span_sums_to_the_window_idle(case):
    events = ({"nested": nested, "unspanned": unspanned}[case]()
              if case in ("nested", "unspanned")
              else traces.load(str(RECORDED[case])))
    r = traces.reduce(events)
    idle = spans.idle_by_span(events, r["lo"], r["hi"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-9)


def test_phase_seconds_clips_spans_to_the_window():
    events = nested()
    assert us(spans.phase_seconds(events, 0, 100e3)) == {
        "engine.round": 100.0, "engine.plan": 50.0,
        "engine.block_sync": 20.0}


HLO = """HloModule jit_spec_decode_round_impl, is_scheduled=true

%add.r (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(f)/reject/add"}
}

%body.3 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %copy.200 = f32[8]{0} copy(%p)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%p, %copy.200)
}

ENTRY %main.7 (x: f32[8]) -> f32[] {
  %x = f32[8]{0} parameter(0)
  %while.12 = (s32[], f32[8]{0}) while(%x), condition=%cond.2, body=%body.3, metadata={op_name="jit(spec_decode_round_impl)/verify/while"}
  %reduce.4 = f32[] reduce(%x, %x), to_apply=%add.r, metadata={op_name="jit(spec_decode_round_impl)/signal/reduce_sum"}
  %sum.5 = f32[] reduce(%x, %x), to_apply=%add.r, metadata={op_name="jit(spec_decode_round_impl)/predict/reduce_sum"}
  ROOT %copy.6 = f32[] copy(%reduce.4)
}
"""


def test_stage_map_reads_scopes_and_inherits_through_loops():
    m = spans.stage_map(HLO)
    assert m["while.12"] == ("verify", "(s32[], f32[8]{0})")
    # a loop body's operations take the loop's stage
    assert m["copy.200"] == ("verify", "f32[8]{0}")
    assert m["reduce.4"] == ("signal", "f32[]")
    # its own scope wins over its callers'; a computation called from two
    # stages gives none to an operation without a scope
    assert m["add.9"][0] == "reject"
    assert m["a"][0] is None
    assert m["copy.6"] == (None, "f32[]")


def test_out_shape_of_plain_and_tuple_results():
    assert spans.out_shape("f32[8]{0} add(f32[8]{0} %a)") == "f32[8]{0}"
    assert spans.out_shape("(s32[], f32[2]{0}) while(%t)") == \
        "(s32[], f32[2]{0})"


def test_stage_seconds_reads_each_execution_with_its_program():
    """The same instruction name is verify at one bucket and reject at
    another; the output shape in the trace tells the executions apart."""
    k2 = {"fusion.1": ("verify", "f32[3]{0}"), "copy.2": (None, "f32[1]")}
    k3 = {"fusion.1": ("reject", "f32[4]{0}")}
    events = [
        ev("XLA Modules", "jit_spec_decode_round_impl(1)", 0, 40),
        op(0, 30, "%fusion.1 = f32[3]{0} fusion(f32[3]{0} %p)"),
        op(30, 10, "%copy.2 = f32[1] copy(f32[1] %q)"),
        ev("XLA Modules", "jit_spec_decode_round_impl(2)", 100, 20),
        op(100, 20, "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)"),
        ev("XLA Modules", "jit_prefill_paged_rows(3)", 200, 10),
        op(200, 10, "%fusion.1 = f32[3]{0} fusion(f32[3]{0} %p)"),
    ]
    out = spans.stage_seconds(events, 0, 300e3, [k2, k3])
    assert out.pop("executions") == 2
    assert us(out) == {"program": 60.0, "verify": 30.0, "unscoped": 10.0,
                       "reject": 20.0}
    assert spans.stage_seconds(events, 0, 300e3, []) == {
        "program": 0.0, "executions": 0.0}


def test_stage_map_of_a_tiny_round_program_names_all_six_stages():
    import numpy as np
    from repro.core import spec_decode as sd
    over = tiny.overrides("smollm135m.offline")
    cfg = harness._merge(harness.load_config("smollm-135m"), over["config"])
    eng = harness.build(cfg, 3).engine
    b = eng.serving.max_batch_size
    text = sd.spec_decode_round.lower(
        eng.pt, eng.pd, eng.cfg_t, eng.drafter, eng.spec, 2, eng.state,
        np.zeros((b,), bool)).compile().as_text()
    m = spans.stage_map(text)
    assert {st for st, _ in m.values()} - {None} == set(spans.STAGES)


NEW_METRICS = ("block_sync_idle.offline", "dispatch_idle.offline",
               "engine_cpu_ms.offline", "verify_device_ms.offline")


class FakeRun:
    """What the readers use of a traced run (``harness.RunRecord``)."""

    def __init__(self, events, round_log):
        self.events, self.round_log = events, round_log
        self.trace = traces.reduce(events)
        self.tracer_state = {"start": {"rounds": 0},
                             "stop": {"rounds": len(round_log)}}
        self.system = None

    def window_rounds(self):
        return self.round_log

    def stretch_rounds(self):
        return self.round_log


def _read(run):
    return {m: harness.metric_reader(m)(run) for m in NEW_METRICS}


def _round(k=2, cpu=(0.004, 0.001, 0.002)):
    return {"k": k, "plan_cpu_s": cpu[0], "dispatch_cpu_s": cpu[1],
            "collect_cpu_s": cpu[2]}


def test_new_metrics_read_a_synthetic_traced_run(monkeypatch):
    events = nested() + [
        host("engine.dispatch", 100, 30), host("engine.round_call", 110, 10),
        ev("XLA Modules", "jit_spec_decode_round_impl(1)", 45, 55),
        op(45, 40, "%while.12 = f32[3]{0} while(f32[3]{0} %p)"),
        op(85, 15, "%fusion.7 = f32[1]{0} fusion(f32[3]{0} %p)")]
    stages = {"while.12": ("verify", "f32[3]{0}"),
              "fusion.7": ("reject", "f32[1]{0}")}
    monkeypatch.setattr(spans, "round_stage_maps", lambda run: [stages])
    out = _read(FakeRun(events, [_round(), _round()]))
    assert out["block_sync_idle.offline"] == pytest.approx(100 * 20 / 200)
    # 100-130 idle under dispatch, 10 of it inside its round call
    assert out["dispatch_idle.offline"] == pytest.approx(100 * 30 / 200)
    assert out["engine_cpu_ms.offline"] == pytest.approx(7.0)
    assert out["verify_device_ms.offline"] == pytest.approx(0.040)


def test_new_metrics_read_nothing_on_a_program_without_them(monkeypatch):
    """The parent of this benchmark's readers: no engine spans (only the
    harness's ``bench.*``), no stage scopes, no CPU counters."""
    events = unspanned() + [
        ev("XLA Modules", "jit_spec_decode_round_impl(1)", 0, 10)]
    monkeypatch.setattr(spans, "round_stage_maps", lambda run: [
        {"fusion.1": (None, "f32[3]{0}")}])
    run = FakeRun([e for e in events if not e["name"].startswith("engine")],
                  [{"k": 2, "host_blocked_s": 0.0}])
    assert _read(run) == dict.fromkeys(NEW_METRICS)


STAGES = ROOT / "bench/tests/data/smollm_offline_round_stages.json"


def recorded_run(monkeypatch):
    """One round of the offline cell on the chip (TPU v5 lite, seed
    2147485001) with the engine's spans: the idle stretch after a round
    and the next round's execution, device operations cut to their name
    and output shape, runtime threads left out.  The stage map holds the
    round program's instructions that the excerpt names (draft bucket 2).
    The round log's counters are the traced stretch's means."""
    import json
    maps = [{n: tuple(v) for n, v in m.items()}
            for m in json.loads(STAGES.read_text())]
    monkeypatch.setattr(spans, "round_stage_maps", lambda run: maps)
    events = traces.load(str(RECORDED["engine"]))
    return FakeRun(events, [_round(cpu=(0.088, 0.0, 0.066))])


def test_recorded_engine_trace_attribution(monkeypatch):
    run = recorded_run(monkeypatch)
    att = spans.attribution(run)
    idle = att["idle_by_span_s"]
    assert sum(idle.values()) == pytest.approx(att["idle_s"], rel=1e-9)
    # nearly all of the round's idle is the eager block-table sync; the
    # instants outside every engine span are a sliver of the window
    assert idle["engine.block_sync"] == pytest.approx(0.125898, rel=1e-4)
    assert idle[spans.NO_SPAN] < 0.02 * att["window_s"]
    st = att["stage_ms_per_round"]
    assert att["round_executions"] == 1
    assert set(spans.STAGES) <= set(st)
    assert sum(st[s] for s in spans.STAGES) + st["unscoped"] == \
        pytest.approx(st["program"], rel=1e-3)
    assert sum(st[s] for s in spans.STAGES) > 0.98 * st["program"]


def test_every_new_metric_reads_the_recorded_trace(monkeypatch):
    out = _read(recorded_run(monkeypatch))
    assert all(v is not None and v >= 0 for v in out.values()), out
    assert out["block_sync_idle.offline"] == pytest.approx(18.527, rel=1e-3)
    assert out["dispatch_idle.offline"] < 1.0
    assert out["engine_cpu_ms.offline"] == pytest.approx(154.0)
    assert out["verify_device_ms.offline"] == pytest.approx(546.846,
                                                            rel=1e-4)
