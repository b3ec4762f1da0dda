"""Round program: device milliseconds per speculative round, from the
trace (the executions of the round's jitted program and their device
time on the XLA Modules line)."""

PROGRAM = "spec_decode_round_impl"


def read(run):
    secs, count = run.program_seconds(PROGRAM)
    if not count:
        return None
    return 1000.0 * secs / count
