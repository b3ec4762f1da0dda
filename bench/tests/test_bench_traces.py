"""The trace reduction: device busy time, programs, kernels, idle gaps."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import traces  # noqa: E402

DEV = "/device:TPU:0"


def ev(line, name, start_us, dur_us, plane=DEV, **stats):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3,
            "stats": stats}


def synthetic():
    """Two rounds and a prefill on one chip, host spans around them."""
    return [
        ev("XLA Modules", "jit_spec_decode_round_impl(1)", 0, 40),
        ev("XLA Ops", "fusion.1", 0, 25),
        ev("XLA Ops", "custom-call.3", 20, 15,
           long_name="paged_ragged_verify_attention f32[16,8,44,128]"),
        ev("XLA Modules", "jit_prefill_paged_rows(2)", 50, 30),
        ev("XLA Ops", "fusion.2", 50, 30),
        ev("XLA Modules", "jit_spec_decode_round_impl(1)", 100, 40),
        ev("XLA Ops", "fusion.1", 100, 40),
        ev("python", "bench.plan", 38, 14, plane="/host:CPU"),
        ev("python", "bench.collect", 80, 30, plane="/host:CPU"),
        ev("python", "PjitFunction(x)", 85, 10, plane="/host:CPU"),
        ev("python", "bench.dispatch", 150, 50, plane="/host:CPU"),
    ]


def test_union_seconds_merges_and_clips():
    iv = [(0, 10), (5, 20), (30, 40), (35, 38)]
    assert traces.union_seconds(iv, 0, 100) == pytest.approx(30e-9)
    assert traces.union_seconds(iv, 8, 32) == pytest.approx(14e-9)


def test_reduce_synthetic_trace():
    events = synthetic()
    r = traces.reduce(events)
    # window: first event (0) to the last end (200 us); ops busy 35+30+40
    assert r["window_s"] == pytest.approx(200e-6)
    assert r["busy_s"] == pytest.approx(105e-6)
    assert r["chips"] == 1
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(65e-6)]
    gaps = dict((round(s * 1e6), n) for n, s in r["idle_gaps"])
    # 140-200 idle under bench.dispatch; 35-50 under bench.plan;
    # 80-100: PjitFunction lies inside bench.collect, the bench span wins
    assert gaps == {60: "bench.dispatch", 15: "bench.plan",
                    20: "bench.collect"}
    lo, hi = r["lo"], r["hi"]
    assert traces.program_seconds(events, "spec_decode_round_impl", lo,
                                  hi) == (pytest.approx(80e-6), 2)
    assert traces.program_seconds(events, "prefill_paged", lo,
                                  hi) == (pytest.approx(30e-6), 1)
    k = traces.kernel_events(events, ["paged_ragged_verify_attention"], lo,
                             hi)
    assert [e["name"] for e in k] == ["custom-call.3"]


def test_reduce_averages_over_chips():
    events = synthetic() + [
        dict(e, plane="/device:TPU:1") for e in synthetic()
        if e["plane"] == DEV and e["name"] != "fusion.2"]
    r = traces.reduce(events)
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((105e-6 + 75e-6) / 2)


def test_json_round_trip(tmp_path):
    p = tmp_path / "t.json"
    p.write_text(json.dumps(synthetic()))
    assert traces.load(str(p)) == synthetic()


RECORDED = ROOT / "bench" / "tests" / "data" / "smollm_offline_excerpt.json"


def _brute_busy(events, lo, hi):
    """Busy time from every elementary stretch between two interval ends
    that some operation covers."""
    iv = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
          if e["plane"].startswith("/device:TPU:") and e["line"] == "XLA Ops"]
    cuts = sorted({lo, hi, *(x for p in iv for x in p if lo <= x <= hi)})
    busy = 0.0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e in iv):
            busy += b - a
    return busy / 1e9


def test_recorded_chip_trace():
    """An excerpt of a chip trace of the offline cell (TPU v5 lite),
    between two rounds: the reduction's busy time agrees with a sampled
    count, its gaps are named by the benchmark's host spans, and the
    operation names lose their HLO text."""
    events = traces.load(str(RECORDED))
    r = traces.reduce(events)
    assert r["chips"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["busy_s"] == pytest.approx(
        _brute_busy(events, r["lo"], r["hi"]), rel=1e-9)
    assert {name for name, _ in r["idle_gaps"]} <= {"bench.plan",
                                                    "bench.collect",
                                                    "bench.dispatch"}
    assert all(" = " not in name for name, _ in r["device_ops"])
    assert len(r["device_ops"]) == 10
