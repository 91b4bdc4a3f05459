"""In the paper-mix cell, the share of the traced window in which the device
ran no operation, read as ``device_idle.ingest`` reads it
(``bench/metrics/device_idle.ingest.py``)."""
import harness


def read(ctx):
    return harness.metric_reader("device_idle.ingest").read(ctx)
