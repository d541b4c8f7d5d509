"""Device-idle milliseconds per train step under the program's training
loop spans (``gs.fit.*``, program_trace.py): the time the chip waits on
``fit_partitions``' host code."""

import program_trace


def read(run):
    t = program_trace.for_run(run)
    steps = run.notes.get("window_steps")
    if t is None or not steps:
        return None
    idle = program_trace.idle_under(t, "gs.fit.")
    return None if idle is None else 1e3 * idle / steps
