"""Device: the share of the traced window in which no operation ran on
the chip, in the offline cells; the same reading as
``device_idle.serve``, which moves ``output_tok_s`` here."""
from bench import harness

read = harness.metric_reader("device_idle.serve")
