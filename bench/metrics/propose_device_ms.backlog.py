"""Round program: device milliseconds per round execution of the
operations under the round's ``propose`` scope (the drafter's steps: for
a model drafter, K + 1 single-token decodes of the draft over its own
paged pool), from the trace (``bench/spans.py``: ``stage_seconds``).  A
program without the stage scopes reads nothing."""
from bench import spans


def read(run):
    return spans.attribution(run)["stage_ms_per_round"].get("propose")
