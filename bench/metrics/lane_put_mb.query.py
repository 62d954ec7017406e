"""Megabytes per ``QueryEngine`` count pass shipped between host and
device (``QueryStats.device_transfer_bytes`` of the last pass, from the
kernel ledger): every program call's inputs and the results read back."""

LAYER = "Lanes"
UNIT = "MB/pass"
SOURCE = "program_counter"
MOVES = "count_s"


def read(run):
    shipped = getattr(run.stats, "device_transfer_bytes", 0)
    if not shipped:
        return None
    return shipped / 1e6
