"""Round program: the share of the draft row-steps a round dispatched
that the rows' SL asked for: the round log's ``proposed`` (draft tokens
proposed by the rows that did work) over ``b_eff`` x ``k`` (rows carried
times the bucket's draft positions), summed over the window's drafting
rounds, in percent.  At T > 0 the pipelined engine dispatches every
round at the policy's largest bucket, so the rest is masked padding."""


def read(run):
    rounds = [r for r in run.window_rounds() if r["k"] > 0]
    dispatched = sum(r["b_eff"] * r["k"] for r in rounds)
    if not dispatched:
        return None
    return 100.0 * sum(r["proposed"] for r in rounds) / dispatched
