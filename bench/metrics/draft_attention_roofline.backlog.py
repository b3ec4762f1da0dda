"""Kernels: the paged attention kernel's share of its roofline over the
draft model's decode calls (``kernels/ragged_attention.py``, one call a
draft layer and step, one query position a row), in the traced stretch.

A call is an operation of the kernel itself, found by its own name and
output shape (``[rows, KV, G, hd]`` at the draft's heads), not by the
statistics the trace attaches to it: operations around the kernel (its
page view, the reshapes of its output) also carry its name and shapes
there.  For each call the least time the chip could take is the larger
of its operations over the bf16 peak and its bytes over the HBM
bandwidth (``bench/peaks.json``), from ``flops.paged_attention_call`` at
the draft's shapes and the pool tiles the stretch's rounds held on
average (the draft's pool mirrors the target's blocks); the share is the
sum of those times over the calls' device time, in percent."""
from typing import Dict, List

from bench import flops, harness, spans, traces

KERNEL = "paged_ragged_verify_attention"


def calls(run, cfg: Dict, t: int) -> List[Dict]:
    """The kernel's own calls at ``t`` query positions a row for the model
    ``cfg``: the TPU trace names an operation by its HLO text, whose
    output shape is ``[rows, KV, G * t, hd]``."""
    kv, hd = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    g = int(cfg["num_attention_heads"]) // kv
    shape = f"[{run.max_batch},{kv},{g * t},{hd}]"
    return [e for e in run.kernel_events([KERNEL])
            if traces.op_name(e).lstrip("%").startswith(KERNEL)
            and shape in spans.out_shape(e["name"].split(" = ", 1)[-1])]


def least_seconds(run, cfg: Dict, t: int, rounds: List[Dict]) -> float:
    """The least time of one call at the mean pool tiles of ``rounds``."""
    ctx = (sum(r["kv_blocks_in_use"] for r in rounds) / len(rounds)
           * run.system.serving.kv_block_size)
    work = flops.paged_attention_call(cfg, run.max_batch, t, int(ctx))
    return max(work["flops"] / run.peaks["bf16_flops"],
               work["bytes"] / run.peaks["hbm_bytes_per_s"])


def read(run):
    draft = run.cfg.get("draft")
    rounds = run.stretch_rounds()
    if draft is None or not rounds:
        return None
    dcfg = harness.load_config(draft) if isinstance(draft, str) else draft
    found = calls(run, dcfg, 1)
    busy = sum(e["dur_ns"] for e in found) / 1e9
    if busy <= 0:
        return None
    return 100.0 * len(found) * least_seconds(run, dcfg, 1, rounds) / busy
