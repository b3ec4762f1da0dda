"""Kernels: the paged verify-attention kernel's share of its roofline in
the offline cells; the same reading as ``paged_verify_roofline.serve``,
which moves ``output_tok_s`` here."""
from bench import harness

read = harness.metric_reader("paged_verify_roofline.serve")
