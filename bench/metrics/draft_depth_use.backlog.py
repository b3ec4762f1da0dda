"""Round program: the share of the dispatched draft scan depth that any
row of a round used: the round log's ``draft_steps_effective`` (the most
draft tokens a live row proposed, plus the step that writes the last
one's keys) over ``k + 1`` (the scan's steps), summed over the window's
drafting rounds, in percent.  A round log without the counter reads
nothing."""


def read(run):
    rounds = [r for r in run.window_rounds()
              if r["k"] > 0 and "draft_steps_effective" in r]
    if not rounds:
        return None
    return 100.0 * sum(r["draft_steps_effective"] for r in rounds) / sum(
        r["k"] + 1 for r in rounds)
