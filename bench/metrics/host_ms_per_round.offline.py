"""Engine host path: milliseconds of CPU time per round that the driving
thread spent in ``pump()`` over the window (the thread's CPU clock): the
engine's planning, dispatch and reconciliation in Python and JAX's
dispatch of its programs.  A wait for the device sleeps and does not
count, so a round that waits on the chip or on device memory does not
move it; the host spans' wall time does not isolate this (PERF.md)."""


def read(run):
    rounds = run.window_rounds()
    if not rounds or run.window.pump_cpu_s <= 0:
        return None
    return 1000.0 * run.window.pump_cpu_s / len(rounds)
