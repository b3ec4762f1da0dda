"""Kernels: the share of the block table that the paged verify kernel
swept over the window's rounds: the round log's ``verify_blocks_swept``
(each carried row's blocks up to its last query position) over the rows
carried times the table's width, in percent.  A round log without the
counter reads nothing."""


def read(run):
    rounds = [r for r in run.window_rounds() if "verify_blocks_swept" in r]
    carried = sum(r["b_eff"] for r in rounds)
    if not carried:
        return None
    return 100.0 * sum(r["verify_blocks_swept"] for r in rounds) / (
        carried * run.system.serving.blocks_per_seq())
