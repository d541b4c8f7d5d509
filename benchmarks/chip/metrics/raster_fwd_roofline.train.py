"""Share of its roofline the forward rasterizer kernel reached in the
training window: the compositing work the window's views need (work.py,
the benchmark's own overlap count) at the chip's peaks, over the summed
device time of every tier's forward kernel launch."""

import reduce_trace
import work


def read(run):
    if not run.work:
        return None
    seconds = reduce_trace.kernel_seconds(run.red, "fwd")
    share, _ = work.roofline_share(*run.work["raster_fwd"], seconds,
                                   run.peak)
    return share
