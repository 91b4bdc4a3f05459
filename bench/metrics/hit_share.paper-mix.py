"""In the paper-mix cell, the share of the window's ingested DIMMs served on
the HIT path, read as ``hit_share.ingest`` reads it
(``bench/metrics/hit_share.ingest.py``)."""
import harness


def read(ctx):
    return harness.metric_reader("hit_share.ingest").read(ctx)
