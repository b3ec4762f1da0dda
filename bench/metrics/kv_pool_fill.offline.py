"""KV pool: the mean share of the target pool's blocks in use over the
window's rounds (round log ``kv_blocks_in_use`` over the pool's block
count), in percent."""


def read(run):
    rounds = run.window_rounds()
    if not rounds or not run.kv_blocks_total:
        return None
    return 100.0 * sum(r["kv_blocks_in_use"] for r in rounds) / (
        len(rounds) * run.kv_blocks_total)
