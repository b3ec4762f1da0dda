"""Engine host path: milliseconds of the driving thread's CPU time per
round that the engine's own phases spent, from its round log
(``plan_cpu_s`` + ``dispatch_cpu_s`` + ``collect_cpu_s``, each booked
to the round it served) over the window's rounds.  A wait sleeps and
does not count.  A round log without the counters reads nothing."""
from bench import spans


def read(run):
    rounds = [r for r in run.window_rounds() if "plan_cpu_s" in r]
    if not rounds:
        return None
    return 1000.0 * sum(r[f"{p}_cpu_s"] for r in rounds
                        for p in spans.PHASES) / len(rounds)
