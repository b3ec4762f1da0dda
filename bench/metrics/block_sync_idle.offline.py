"""Engine host path: the share of the traced window in which the device
was idle while the engine synchronised block tables
(``engine.block_sync``: the eager ``kv_pos`` resets and table-row
writes of ``ServingEngine._sync_block_tables``), in percent.  An idle
instant is charged to the innermost engine span covering it
(``bench/spans.py``); a trace without engine spans reads nothing."""
from bench import spans

SPANS = ("engine.block_sync",)


def read(run):
    return spans.idle_share(run, SPANS)
