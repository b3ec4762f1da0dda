"""Round program yield: tokens emitted per sequence-round over the
window's rounds (round log: emitted tokens over the rows each round
carried)."""


def read(run):
    rounds = run.window_rounds()
    rows = sum(r["b_eff"] for r in rounds)
    if not rows:
        return None
    return sum(r["emitted"] for r in rounds) / rows
