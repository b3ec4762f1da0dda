"""The ``granite8b-l9.chat-backlog`` cell: driven on the CPU at a tiny
shape (a Granite-shaped target with 4:1 grouped heads and a model draft
sampled at T = 1, over the chat mix with a standing queue), its check, a
planted fault and the per-layer counters of the draft's work; and its
trace readers on one round recorded on the chip."""
import copy
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops, harness, loadgen, spans, traces  # noqa: E402
from bench.tests import tiny  # noqa: E402

CELL = "granite8b-l9.chat-backlog"
CONFIG = "granite-8b-l9-sd"             # the cell's configuration
# granite's grouping (4 query heads to a KV head) and head size, and
# SmolLM's grouping (3 to 1).  The target is wide enough that its logits
# spread as a full-width model's do (0.02 * sqrt(hidden_size) at random
# weights), so that a planted token fault shows in a few hundred tokens.
TARGET = dict(tiny.TARGET, hidden_size=1024, num_attention_heads=8,
              num_key_value_heads=2, head_dim=128, intermediate_size=256)
DRAFT = dict(tiny.DRAFT, hidden_size=48, num_attention_heads=3,
             num_key_value_heads=1)
SERVING = dict(tiny.SERVING, max_seq_len=256)
CHECK = dict(tiny.CHECK, token_requests=8, min_tokens=400)
MIX = {"queue": 48, "fixed_head": 4,
       "prompt_tokens": {"median": 24, "min": 8, "max": 40},
       "output_tokens": {"median": 60, "min": 16, "max": 100}}


def overrides() -> dict:
    cfg = dict(TARGET, serving=SERVING, check=CHECK)
    draft = harness.load_config("smollm-135m")
    cfg["draft"] = dict(copy.deepcopy(draft), **DRAFT)
    return {"config": cfg, "mix": MIX, "peaks": tiny.PEAKS}


def run(seed, **kw):
    # a window short enough that the queue is still standing at its close,
    # so that live rows' keys and values are checked
    return harness.run_cell(CELL, seed, 2.0, kw.pop("trace", False),
                            time.monotonic(), require_tpu=False,
                            overrides=overrides(), **kw)


@pytest.fixture(scope="module")
def controlled():
    """One untraced run that also computes the control's numbers and a
    planted token fault's."""
    return run(2**31 + 16, control=True)


def test_cell_is_correct_at_tiny_size(controlled):
    out, info = controlled, controlled["_info"]
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert out["metrics"]["output_tok_s"]["value"] > 0
    assert info["window_compiles"] == 0, info["window_compiled"]
    assert set(out["check"]) == {"kv_err", "sample_z"}
    assert info["unfinished"] == 0 and info["requests"] == 48
    assert info["checked_kv"] >= 1 and info["checked_tokens"] > 0


def test_planted_token_fault_fails_sample_z(controlled):
    limit = controlled["check"]["sample_z"]["limit"]
    assert controlled["check"]["sample_z"]["value"] <= limit
    assert controlled["_info"]["token_fault"]["sample_z"] > limit


def test_control_fails_kv_err(controlled):
    limit = controlled["check"]["kv_err"]["limit"]
    assert controlled["check"]["kv_err"]["value"] <= limit
    assert controlled["_info"]["control"]["kv_err"] > limit


def test_every_planned_request_fits_the_sequence_budget():
    """A request the scheduler refuses counts as failed: over the mix's
    256 planned lengths, prompt + output + sl_max + 1 stays within the
    2048 positions a slot holds, whichever request gets which length."""
    from repro.core.config import SpecDecodeConfig
    cfg = harness.load_config(CONFIG)
    mix = loadgen.load_mix("chat-backlog")
    prompts, outputs = loadgen.length_sets(mix, 51.0)
    assert len(prompts) == 256
    extra = SpecDecodeConfig().sl_max + 1
    budget = cfg["serving"]["max_seq_len"]
    assert int(prompts.max() + outputs.max()) + extra <= budget
    for seed in (1, 2**31 + 5):
        planned = loadgen.plan(mix, 51.0, seed, int(cfg["vocab_size"]))
        assert all(len(p.prompt) + p.max_new_tokens + extra <= budget
                   for p in planned)


def test_cell_config_is_granite_l9_warmed_for_wider_admissions():
    """The cell runs a copy of ``granite-8b-l9`` that differs in its name
    and in the prefill rows set-up warms: a standing queue behind a full
    pool admits up to four or five rows of one prompt bucket at once, and
    a group wider than warm-up ran compiles inside the window."""
    entry = harness.find_cell(harness.load_json(ROOT / "BENCHMARK.json"),
                              CELL)["config_entry"]
    assert entry["name"] == CONFIG
    cell, base = harness.load_config(entry), harness.load_config(
        "granite-8b-l9")
    assert cell.pop("name") == CONFIG and base.pop("name") == "granite-8b-l9"
    assert cell["serving"].pop("warm_rows") == 5
    base["serving"].pop("warm_rows")
    assert cell == base


def test_traced_run_reads_the_draft_counters():
    out = run(2**31 + 17, trace=True)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in ("draft_step_use.backlog", "draft_depth_use.backlog",
                 "batch_occupancy.backlog"):
        assert 0 < m[name] <= 100, name
    k = 10                                   # sl_max: every round's bucket
    assert 0 < m["tokens_per_round.backlog"] <= k + 1
    assert m["mfu.backlog"] > 0
    # the CPU trace has no device plane: the kernels' readers find nothing
    assert "draft_attention_roofline.backlog" not in m


# ---------------------------------------------------------------------------
# the readers, on one round of the cell recorded on the chip
# ---------------------------------------------------------------------------

DATA = ROOT / "bench" / "tests" / "data"
DRAFT_CALL = "[16,3,3,64]"          # a draft decode call: [rows, KV, G, hd]
VERIFY_CALL = "[16,8,44,128]"       # a verify call: [rows, KV, G * 11, hd]
# the recorded round's stage times (ms), as ``spans.stage_seconds`` read
# them from the whole trace before the excerpt was cut
PROPOSE_MS, VERIFY_MS = 130.477909, 31.443975


class RecordedRun:
    """What the readers use of a traced run (``harness.RunRecord``), over
    one round of ``granite8b-l9.chat-backlog`` on the chip (TPU v5 lite):
    the device events from the start of a round execution in the middle
    of the traced stretch (seed 2147491602) to the start of the next,
    with the engine's spans over them, operation names cut to their
    output shape.  Operations that lie inside another of the same stage
    (a loop's body) are left out, but for the kernel's calls: the busy
    time, idle attribution, stage times and kernel calls read the same
    as on the whole excerpt.  The meta file holds the stretch's round-log
    means, the batch, the block size, the device's peaks and the round
    program's stage map restricted to the operations kept."""

    def __init__(self, events=None):
        import json
        self.meta = json.loads((DATA / "granite_backlog_round_meta.json")
                               .read_text())
        self.events = (events if events is not None else
                       traces.load(str(DATA / "granite_backlog_round.json")))
        self.trace = traces.reduce(self.events)
        self.cfg = harness.load_config(CONFIG)
        self.max_batch = self.meta["max_batch"]
        self.peaks = self.meta["peaks"]
        self.round_log = [{"k": k, "b_eff": self.meta["b_eff"],
                           "kv_blocks_in_use": self.meta["kv_blocks_in_use"]}
                          for k in self.meta["k"]]
        self.tracer_state = {"start": {"rounds": 0},
                             "stop": {"rounds": len(self.round_log)}}
        self.system = SimpleNamespace(serving=SimpleNamespace(
            kv_block_size=self.meta["kv_block_size"]))

    def stretch_rounds(self):
        return self.round_log

    window_rounds = stretch_rounds

    def kernel_events(self, substrings):
        return traces.kernel_events(self.events, substrings,
                                    self.trace["lo"], self.trace["hi"])

    def without(self, shape):
        return RecordedRun([e for e in self.events if shape not in e["name"]])

    def with_wrappers(self):
        """The run with, after each kernel call, a short operation whose
        trace statistics name the kernel and the call's shape, as the
        operations around a Pallas call carry them on the chip."""
        extra = []
        for e in self.kernel_events(["paged_ragged_verify_attention"]):
            shape = e["name"].split(" = ", 1)[1].split("{", 1)[0]
            extra.append({
                "plane": e["plane"], "line": e["line"],
                "name": "%reshape.9 = f32[16,1,9,64]{3,2,1,0}",
                "start_ns": e["start_ns"] + e["dur_ns"], "dur_ns": 1e3,
                "stats": {"tf_op": "jit(spec_decode_round_impl)/"
                                   "paged_ragged_verify_attention/reshape",
                          "long_name": f"%reshape.9 = reshape({shape} %x)"}})
        return RecordedRun(self.events + extra)


def _calls(run, shape):
    return [e for e in run.kernel_events(["paged_ragged_verify_attention"])
            if shape in e["name"]]


def _share(run, cfg, calls, t):
    """The roofline share of ``calls`` at ``t`` query positions, counted
    here from the flops module and the recorded means."""
    ctx = int(run.meta["kv_blocks_in_use"] * run.meta["kv_block_size"])
    work = flops.paged_attention_call(cfg, run.max_batch, t, ctx)
    least = max(work["flops"] / run.peaks["bf16_flops"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * len(calls) * least / (
        sum(e["dur_ns"] for e in calls) / 1e9)


def test_recorded_round_holds_both_models_kernel_calls():
    run = RecordedRun()
    assert run.meta["k"] == [10]
    # eleven draft steps of 30 SmolLM layers; one verify call per target
    # layer
    assert len(_calls(run, DRAFT_CALL)) == 11 * 30
    assert len(_calls(run, VERIFY_CALL)) == 9


def test_draft_roofline_counts_only_the_draft_calls():
    read = harness.metric_reader("draft_attention_roofline.backlog")
    run = RecordedRun()
    value = read(run)
    want = _share(run, harness.load_config("smollm-135m"),
                  _calls(run, DRAFT_CALL), 1)
    assert value == pytest.approx(want, rel=1e-9)
    assert 0 < value <= 100
    assert read(run.without(VERIFY_CALL)) == pytest.approx(value)
    assert read(run.with_wrappers()) == pytest.approx(value)
    assert read(run.without(DRAFT_CALL)) is None


def test_verify_roofline_counts_only_the_target_calls():
    read = harness.metric_reader("paged_verify_roofline.backlog")
    run = RecordedRun()
    value = read(run)
    want = _share(run, run.cfg, _calls(run, VERIFY_CALL), 11)
    assert value == pytest.approx(want, rel=1e-9)
    assert 0 < value <= 100
    assert read(run.without(DRAFT_CALL)) == pytest.approx(value)
    assert read(run.with_wrappers()) == pytest.approx(value)
    assert read(run.without(VERIFY_CALL)) is None


def test_stage_readers_read_the_recorded_round(monkeypatch):
    run = RecordedRun()
    maps = [{n: tuple(v) for n, v in m.items()} for m in run.meta["maps"]]
    monkeypatch.setattr(spans, "round_stage_maps", lambda r: maps)
    propose = harness.metric_reader("propose_device_ms.backlog")(run)
    verify = harness.metric_reader("verify_device_ms.backlog")(run)
    st = spans.attribution(run)["stage_ms_per_round"]
    assert spans.attribution(run)["round_executions"] == 1
    assert (propose, verify) == (st["propose"], st["verify"])
    assert propose == pytest.approx(PROPOSE_MS, rel=1e-6)
    assert verify == pytest.approx(VERIFY_MS, rel=1e-6)
    assert propose + verify < st["program"]
