"""Share of a ``QueryEngine`` cell's window in which the device ran no
operation, from the profiler trace."""

LAYER = "Device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "count_s"


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
