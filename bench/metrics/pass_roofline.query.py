"""The least device time any implementation needs for one ``QueryEngine``
count pass, as a share of the device's busy seconds per pass.

The least time is the stored graph read once from HBM: 4 bytes for
each stored edge and each vertex (the benchmark's own reference graph),
over the chip's published HBM bandwidth, whatever the pattern and
whatever lane implements it. No published peak fits int32 compares, so
there is no operations term; the bytes term bounds every
implementation, so the share cannot pass 100%."""

LAYER = "Kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "count_s"


def least_seconds(graph_words: int, peaks: dict) -> float:
    """The stored graph read once from HBM."""
    return 4.0 * graph_words / peaks["hbm_bytes_per_s"]


def read(run):
    if run.trace is None or not run.peaks or run.trace.busy_s <= 0:
        return None
    return 100.0 * least_seconds(run.graph_words, run.peaks) \
        / (run.trace.busy_s / run.passes)
