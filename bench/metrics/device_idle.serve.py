"""Device: the share of the traced window in which no operation ran on
the chip (1 - union of the trace's operation intervals / window), in
percent."""


def read(run):
    t = run.trace
    if t["window_s"] <= 0 or not t["chips"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
