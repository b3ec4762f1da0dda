"""Engine host path: the share of the traced window in which the device
was idle while the engine dispatched a round (``engine.dispatch`` and
its ``engine.round_call``: the jitted round's call, the wait for its
arguments' memory, and the host copies it starts), in percent.  An idle
instant is charged to the innermost engine span covering it
(``bench/spans.py``); a trace without engine spans reads nothing."""
from bench import spans

SPANS = ("engine.dispatch", "engine.round_call")


def read(run):
    return spans.idle_share(run, SPANS)
