"""Scheduler batch occupancy: the mean share of the engine's slots that
the window's rounds carried (round log ``b_eff`` over
``max_batch_size``), in percent."""


def read(run):
    rounds = run.window_rounds()
    if not rounds:
        return None
    return 100.0 * sum(r["b_eff"] for r in rounds) / (len(rounds)
                                                       * run.max_batch)
