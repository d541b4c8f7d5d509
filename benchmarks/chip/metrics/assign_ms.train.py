"""Device milliseconds of tile assignment per train step: the self time of
every op under the program's ``gs.assign`` scope (program_trace.py) over
the window's steps."""

import program_trace


def read(run):
    t = program_trace.for_run(run)
    steps = run.notes.get("window_steps")
    if t is None or not steps or "assign" not in t["scope_s"]:
        return None
    return 1e3 * t["scope_s"]["assign"] / steps
