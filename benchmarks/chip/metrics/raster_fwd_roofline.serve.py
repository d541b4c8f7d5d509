"""Share of its roofline the forward rasterizer kernel reached while
serving: the compositing work of the served requests (work.py, from each
request's bucket pose and LOD rung) over the kernel's summed device time."""

import reduce_trace
import work


def read(run):
    if not run.work:
        return None
    seconds = reduce_trace.kernel_seconds(run.red, "fwd")
    share, _ = work.roofline_share(*run.work["raster_fwd"], seconds,
                                   run.peak)
    return share
