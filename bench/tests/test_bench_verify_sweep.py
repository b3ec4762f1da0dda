"""The share of the block table that the paged verify kernel sweeps
(``bench/metrics/verify_sweep_share.offline.py``), on synthetic round
logs."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402

read = harness.metric_reader("verify_sweep_share.offline")


def run(rounds, width=128):
    serving = SimpleNamespace(blocks_per_seq=lambda: width)
    return SimpleNamespace(window_rounds=lambda: rounds,
                           system=SimpleNamespace(serving=serving))


def test_share_is_blocks_swept_over_rows_times_table_width():
    rounds = [{"b_eff": 128.0, "verify_blocks_swept": 2950.0},
              {"b_eff": 64.0, "verify_blocks_swept": 1000.0}]
    assert read(run(rounds)) == pytest.approx(
        100 * 3950 / (192 * 128))
    assert read(run(rounds, width=32)) == pytest.approx(
        100 * 3950 / (192 * 32))


def test_a_round_log_without_the_counter_reads_nothing():
    """The parent program books no ``verify_blocks_swept``; a window
    with no carried row has nothing to share."""
    assert read(run([{"b_eff": 128.0, "kv_blocks_in_use": 2900.0}])) is None
    assert read(run([])) is None


def test_traced_offline_run_reports_the_share():
    """The engine books the counter each round, so a traced run of the
    offline cell at tiny size reports the share beside the other
    counters' readings."""
    out = tiny.run("smollm135m.offline", seed=2**31 + 11, trace=True)
    assert 0 < out["metrics"]["verify_sweep_share.offline"]["value"] <= 100
