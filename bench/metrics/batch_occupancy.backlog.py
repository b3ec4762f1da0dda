"""Scheduler batch occupancy in the backlog cell, where the pool bounds
the batch; the same reading as ``batch_occupancy.offline``."""
from bench import harness

read = harness.metric_reader("batch_occupancy.offline")
