"""A cell's run function, driven on the CPU at a tiny shape for its
control flow; the command's refusals; cells, mixes and metrics found by
name."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, loadgen  # noqa: E402
from bench.tests import tiny  # noqa: E402


@pytest.mark.parametrize("workload", ["granite8b-l9.chat",
                                      "smollm135m.offline"])
def test_cell_run_function_at_tiny_size(workload, tmp_path):
    root = tiny.bench_root(tmp_path)
    out = tiny.run(workload, seed=2**31 + 7, root=root)
    info = out["_info"]
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0
    bm = harness.load_benchmark(root)
    want = {m["name"] for m in bm["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert info["window_compiles"] == 0, info["window_compiled"]
    assert info["checked_kv"] >= 1 and info["checked_tokens"] >= 40
    assert list(out)[-2:] == ["check", "_info"]
    assert 0 < info["pool_fill_mean"] <= info["pool_fill_max"] <= 1
    assert set(out["check"]) == {"kv_err", "token_gap" if "offline" in
                                 workload else "sample_z"}


def test_open_loop_stamps_arrival_at_due_time():
    """Each request's arrival time is the moment it was due, however late
    the generator submitted it."""
    import numpy as np
    over = tiny.overrides("granite8b-l9.chat")
    cfg = harness._merge(harness.load_config("granite-8b-l9"),
                         over["config"])
    mix = harness._merge(loadgen.load_mix("chat"), over["mix"])
    system = harness.build(cfg, 5)
    planned = loadgen.plan(mix, 1.0, 5, int(cfg["vocab_size"]))
    w = harness.drive_open_loop(system, planned, 1.0, None, None, 1,
                                np.random.default_rng(0),
                                harness.CompileLog())
    for p, r in zip(planned, w.requests):
        assert r.arrival_time == pytest.approx(w.t0 + p.due_s, abs=1e-9)
        assert r.first_token_time >= r.arrival_time
    assert len(w.lateness) == len(planned)


def test_trace_run_reads_per_layer_metrics_present_on_cpu():
    """A traced run reports only per-layer metrics; on the CPU the trace
    has no device plane, so the device readers find nothing and the
    counters' readers still report."""
    out = tiny.run("smollm135m.offline", seed=4, trace=True)
    assert {"batch_occupancy.offline", "kv_pool_fill.offline",
            "host_ms_per_round.offline", "mfu.offline"} <= set(out["metrics"])
    assert 0 < out["metrics"]["kv_pool_fill.offline"]["value"] <= 100
    assert "device_idle.offline" not in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert "breakdown" in out


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm135m.offline",
         "--seed", "1", "--seconds", "1"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_cli_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_new_cell_mix_and_metric_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric dropped in as
    files, with entries in BENCHMARK.json, run with no edit to the
    harness."""
    tiny.bench_root(tmp_path)
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/smollm-135m.json").read_text())
    cfg["name"] = "smollm-135m-b"
    (tmp_path / "bench/configs/smollm-135m-b.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/trickle.json").write_text(json.dumps(
        {"arrival": "poisson", "rate_rps": 3.0,
         "prompt_tokens": {"dist": "uniform", "min": 8, "max": 24},
         "output_tokens": {"dist": "uniform", "min": 20, "max": 30}}))
    (tmp_path / "bench/metrics/rounds_seen.py").write_text(
        "def read(run):\n    return float(len(run.window_rounds()))\n")
    bm["configs"].append({"name": "smollm-135m-b", "source": "x",
                          "file": "bench/configs/smollm-135m-b.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "smollm-b.trickle",
                            "config": "smollm-135m-b", "traffic": "trickle",
                            "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "rounds_seen", "unit": "rounds",
                            "better": "higher", "source": "program_counter",
                            "layer": "round program", "moves": "ttft_p95_s",
                            "workloads": ["smollm-b.trickle"]})
    for m in bm["end_to_end"]:
        if "workloads" in m and m["name"] != "output_tok_s":
            m["workloads"].append("smollm-b.trickle")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    over = tiny.overrides("smollm135m.offline")
    over["mix"] = {}
    out = tiny.run("smollm-b.trickle", seed=6, trace=True, root=tmp_path,
                   overrides=over)
    assert out["correct"]
    assert out["metrics"]["rounds_seen"]["value"] > 0
    out = tiny.run("smollm-b.trickle", seed=6, root=tmp_path,
                   overrides=over)
    assert {"ttft_p95_s", "tpot_p95_s", "setup_s"} == set(out["metrics"])
