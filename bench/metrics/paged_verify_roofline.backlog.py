"""Kernels: the paged verify-attention kernel's share of its roofline
over the target's verify calls (``[rows, KV, G * (K + 1), hd]``) in the
backlog cell, counted as ``draft_attention_roofline.backlog`` counts the
draft's: the kernel's own operations by name and output shape, the least
time of each at its draft bucket's mean pool tiles."""
from bench import harness

_roofline = harness.load_module(
    harness.BENCH / "metrics" / "draft_attention_roofline.backlog.py")


def read(run):
    rounds = run.stretch_rounds()
    least = busy = 0.0
    for k in sorted({r["k"] for r in rounds}):
        found = _roofline.calls(run, run.cfg, k + 1)
        at_k = [r for r in rounds if r["k"] == k]
        least += len(found) * _roofline.least_seconds(run, run.cfg, k + 1,
                                                      at_k)
        busy += sum(e["dur_ns"] for e in found) / 1e9
    if busy <= 0:
        return None
    return 100.0 * least / busy
