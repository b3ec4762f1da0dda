"""Whole step: the target's forward FLOPs over the traced stretch against
the chip's bf16 peak, in the backlog cell; the same reading as
``mfu.serve``, which moves ``output_tok_s`` here."""
from bench import harness

read = harness.metric_reader("mfu.serve")
