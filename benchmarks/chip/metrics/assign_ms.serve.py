"""Device milliseconds of the serving miss path (the jitted assignment
table extraction: project + assign) per request served in the window."""

import reduce_trace

MODULE = "jit_tables"


def read(run):
    served = run.work.get("served") if run.work else None
    if not served:
        return None
    return 1e3 * reduce_trace.module_seconds(run.red, MODULE) / served
