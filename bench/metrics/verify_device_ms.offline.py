"""Round program: device milliseconds per round execution of the
operations under the round's ``verify`` scope (the target's forward
over the K + 1 positions, the paged verify kernel included), from the
trace (``bench/spans.py``: ``stage_seconds``).  A program without the
stage scopes reads nothing."""
from bench import spans


def read(run):
    return spans.attribution(run)["stage_ms_per_round"].get("verify")
