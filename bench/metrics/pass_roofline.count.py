"""The least device time any implementation needs for one count pass,
as a share of the device's busy seconds per pass.

The least time is the stored graph read once from HBM: 4 bytes for
each stored edge and each vertex (the benchmark's own reference graph),
over the chip's published HBM bandwidth. No published peak fits int32
compares, so there is no operations term; the bytes term bounds every
implementation, so the share cannot pass 100%."""

LAYER = "Kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "count_s"


def read(run):
    if run.trace is None or not run.peaks or run.trace.busy_s <= 0:
        return None
    least_s = 4.0 * run.graph_words / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (run.trace.busy_s / run.passes)
