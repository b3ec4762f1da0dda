"""Whole step: the target model's forward FLOPs for the tokens the traced
stretch emitted (one forward per emitted token at its context) and the
prompt tokens it prefilled, over the stretch's seconds times the chip's
bf16 peak (``bench/peaks.json``: ``bf16_flops``), in percent.  Draft
FLOPs and rejected positions do not count."""
from bench import flops


def read(run):
    st = run.tracer_state
    if "start" not in st or "stop" not in st:
        return None
    seconds = st["stop"]["t"] - st["start"]["t"]
    dec, dec_ctx, pre, pre_ctx = run.stretch_tokens()
    if seconds <= 0 or not (dec or pre):
        return None
    work = (flops.forward_flops(run.cfg, dec, dec_ctx)
            + flops.forward_flops(run.cfg, pre, pre_ctx))
    return 100.0 * work / (seconds * run.peaks["bf16_flops"] * run.chips)
