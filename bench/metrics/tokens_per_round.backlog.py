"""Round program yield in the backlog cell: tokens emitted per
sequence-round; the same reading as ``tokens_per_round.serve``, which
moves ``output_tok_s`` here."""
from bench import harness

read = harness.metric_reader("tokens_per_round.serve")
