"""Megabytes per count pass the lanes shipped between host and device
(``EngineStats.device_transfer_bytes`` of the last pass, from the kernel
ledger): the padded matrices and edge lists put to the device, and each
result read back."""

LAYER = "Lanes"
UNIT = "MB/pass"
SOURCE = "program_counter"
MOVES = "count_s"


def read(run):
    shipped = getattr(run.stats, "device_transfer_bytes", 0)
    if not shipped:
        return None
    return shipped / 1e6
