"""CPU-sized overrides for driving a cell's run function in tests: the
cell's configuration and mix with widths, depth, batch and lengths cut
far down, and a peaks entry for the CPU (a CPU run is never reported
as a device number; the tests only follow its control flow)."""
import copy
import json
import shutil
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TARGET = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
          "vocab_size": 512}
DRAFT = {"hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 16, "intermediate_size": 64,
         "vocab_size": 512}
# a pool that holds every slot at full length, and prefill warmed for as
# many rows as there are slots: no admission or readmission in a tiny
# window can ask for a program that set-up did not compile
SERVING = {"max_batch_size": 4, "max_seq_len": 128, "pool_fraction": 1.0,
           "warm_rows": 4}
# limits at this size, between its own readings on the CPU: sound runs
# read kv_err up to 3.6e-6, the bf16x3 control 7.4e-5 and more
CHECK = {"kv_requests": 2, "token_requests": 4, "min_tokens": 40,
         "limits": {"kv_err": 2e-5}}
MIXES = {
    "chat": {"rate_rps": 4.0,
             "prompt_tokens": {"median": 24, "min": 8, "max": 40},
             "output_tokens": {"median": 30, "min": 20, "max": 40}},
    "longprompt": {"rate_rps": 2.0,
                   "prompt_tokens": {"min": 33, "max": 60},
                   "output_tokens": {"min": 8, "max": 16}},
    "offline": {"queue": 96, "fixed_head": 4,
                "prompt_tokens": {"min": 8, "max": 40},
                "output_tokens": {"min": 30, "max": 60}},
}
# the open-loop cells written for later (PERF.md, Open questions): their
# entries, for the tests to drive at CPU sizes.  Their mixes state no
# rate until a sweep on the chip finds the knee; MIXES gives one here.
PENDING = {
    "configs": [
        {"name": "granite-8b-l9", "source": "https://arxiv.org/abs/2405.04324", "file": "bench/configs/granite-8b-l9.json", "reduced": ["num_hidden_layers"], "why": "8B dense GQA target at full width, one pipeline stage of 9 of 36 layers, with a SmolLM-135M draft sampled at temperature 1"},
    ],
    "workloads": [
        {"name": "granite8b-l9.chat", "config": "granite-8b-l9", "traffic": "chat", "chips": 1, "why": "chat: open-loop Poisson, prompts lognormal median 256 (32-1536), outputs median 160 (16-512), batch 16; draft steps, wide verify, rejection"},
        {"name": "granite8b-l9.longprompt", "config": "granite-8b-l9", "traffic": "longprompt", "chips": 1, "why": "RAG and summaries: open-loop Poisson, prompts 1025-1920, outputs 16-96, batch 16; prefill does most of the work"},
    ],
    "end_to_end": [
        {"name": "ttft_p95_s", "unit": "s", "better": "lower", "bound": 0.25, "source": "host_clock", "workloads": ["granite8b-l9.chat", "granite8b-l9.longprompt"]},
        {"name": "tpot_p95_s", "unit": "s", "better": "lower", "bound": 0.25, "source": "host_clock", "workloads": ["granite8b-l9.chat", "granite8b-l9.longprompt"]},
    ],
    "per_layer": [
        {"name": "queue_wait_p95_s.serve", "unit": "s", "better": "lower", "source": "program_span", "layer": "front end and scheduler", "moves": "ttft_p95_s", "workloads": ["granite8b-l9.chat", "granite8b-l9.longprompt"]},
        {"name": "tokens_per_round.serve", "unit": "tokens", "better": "higher", "source": "program_counter", "layer": "round program", "moves": "tpot_p95_s", "workloads": ["granite8b-l9.chat", "granite8b-l9.longprompt"]},
        {"name": "round_device_ms.serve", "unit": "ms", "better": "lower", "source": "device_trace", "layer": "round program", "moves": "tpot_p95_s", "workloads": ["granite8b-l9.chat", "granite8b-l9.longprompt"]},
        {"name": "prefill_device_share.serve", "unit": "%", "better": "lower", "source": "device_trace", "layer": "prefill", "moves": "ttft_p95_s", "workloads": ["granite8b-l9.chat", "granite8b-l9.longprompt"]},
        {"name": "paged_verify_roofline.serve", "unit": "%", "better": "higher", "source": "device_trace", "layer": "kernels", "moves": "tpot_p95_s", "workloads": ["granite8b-l9.chat", "granite8b-l9.longprompt"]},
        {"name": "mfu.serve", "unit": "%", "better": "higher", "source": "host_clock", "layer": "whole step", "moves": "tpot_p95_s", "workloads": ["granite8b-l9.chat", "granite8b-l9.longprompt"]},
        {"name": "device_idle.serve", "unit": "%", "better": "lower", "source": "device_trace", "layer": "device", "moves": "tpot_p95_s", "workloads": ["granite8b-l9.chat", "granite8b-l9.longprompt"]},
    ],
}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def overrides(workload: str) -> dict:
    from bench import harness
    mix = workload.split(".", 1)[1]
    cfg = dict(TARGET, serving=SERVING, check=CHECK)
    draft = harness.load_config("smollm-135m")
    cfg["draft"] = dict(copy.deepcopy(draft), **DRAFT)
    return {"config": cfg, "mix": MIXES[mix], "peaks": PEAKS}


def run(workload: str, seed: int = 3, seconds: float = 2.0,
        trace: bool = False, **kw) -> dict:
    from bench import harness
    over = kw.pop("overrides", None) or overrides(workload)
    return harness.run_cell(workload, seed, seconds, trace,
                            time.monotonic(), require_tpu=False,
                            overrides=over, **kw)


def bench_root(tmp_path: Path) -> Path:
    """A checkout-like root whose BENCHMARK.json also holds the ``PENDING``
    cells (written, not yet proven on the chip)."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, entries in PENDING.items():
        bm[key] += copy.deepcopy(entries)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(ROOT / "bench" / d, tmp_path / "bench" / d)
    return tmp_path
