"""Scheduler queue wait: the 95th percentile, over every request due in
the window, of first admission minus the time it was due (request
stamps).  A request never admitted counts as infinitely late."""
import numpy as np


def read(run):
    reqs = run.window_requests()
    if not reqs:
        return None
    waits = [r.admit_time - r.arrival_time if r.admit_time is not None
             else float("inf") for r in reqs]
    return float(np.percentile(waits, 95))
