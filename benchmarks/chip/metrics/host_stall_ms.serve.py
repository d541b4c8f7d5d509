"""Device-idle milliseconds per served request under the server's spans
(``gs.serve.*``, program_trace.py): the time the chip waits on
``GSRenderServer``'s host code (lookups, table readback and upload, image
readback)."""

import program_trace


def read(run):
    t = program_trace.for_run(run)
    served = run.work.get("served") if run.work else None
    if t is None or not served:
        return None
    idle = program_trace.idle_under(t, "gs.serve.")
    return None if idle is None else 1e3 * idle / served
