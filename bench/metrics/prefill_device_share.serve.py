"""Prefill: the share of the traced window in which the device ran the
prefill programs of ``core/prefill.py`` (target and draft), in percent."""

PROGRAMS = ("prefill_paged_rows", "prefill_paged_tail", "prefill_rows")


def read(run):
    window = run.trace["window_s"]
    if window <= 0:
        return None
    return 100.0 * sum(run.program_seconds(p)[0] for p in PROGRAMS) / window
