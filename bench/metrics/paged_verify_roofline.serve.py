"""Kernels: the paged verify-attention kernel's share of its roofline
(``kernels/ragged_attention.py``), over the target model's calls in the
traced stretch.  For a call at draft bucket K the least time the chip
could take is the larger of its operations over the bf16 peak and its
bytes over the HBM bandwidth (``bench/peaks.json``), from
``flops.paged_attention_call`` at the call's shape and the pool tiles
that the stretch's rounds at that bucket held on average; the share is
the sum of those times over the calls' device time, in percent."""
from bench import flops, traces

KERNEL = "paged_ragged_verify_attention"


def read(run):
    cfg = run.cfg
    rounds = run.stretch_rounds()
    if not rounds:
        return None
    b = run.max_batch
    kv, hd = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    g = int(cfg["num_attention_heads"]) // kv
    bs = run.system.serving.kv_block_size
    events = run.kernel_events([KERNEL])
    least = busy = 0.0
    for k in sorted({r["k"] for r in rounds}):
        t = k + 1
        # the TPU trace names an operation by its HLO text, output shape
        # included: the target's verify calls are [rows, KV, G*T, hd]
        shape = f"[{b},{kv},{g * t},{hd}]"
        calls = [e for e in events if shape in traces.op_text(e)]
        at_k = [r for r in rounds if r["k"] == k]
        ctx = sum(r["kv_blocks_in_use"] for r in at_k) / len(at_k) * bs
        work = flops.paged_attention_call(cfg, b, t, int(ctx))
        least += len(calls) * max(
            work["flops"] / run.peaks["bf16_flops"],
            work["bytes"] / run.peaks["hbm_bytes_per_s"])
        busy += sum(e["dur_ns"] for e in calls) / 1e9
    if busy <= 0:
        return None
    return 100.0 * least / busy
