"""In the paper-mix cell, the wall time of discovery, over the traced
window, read as ``discover_share.ingest`` reads it
(``bench/metrics/discover_share.ingest.py``)."""
import harness


def read(ctx):
    return harness.metric_reader("discover_share.ingest").read(ctx)
