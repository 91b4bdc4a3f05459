"""In the paper-mix cell, the device-busy time inside ingest's every-row
sweep, over the traced window, read as ``every_row_busy.ingest`` reads it
(``bench/metrics/every_row_busy.ingest.py``)."""
import harness


def read(ctx):
    return harness.metric_reader("every_row_busy.ingest").read(ctx)
