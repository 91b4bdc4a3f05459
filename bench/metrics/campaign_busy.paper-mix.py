"""In the paper-mix cell, the device-busy time inside the campaign telemetry
phase, over the traced window, read as ``campaign_busy.ingest`` reads it
(``bench/metrics/campaign_busy.ingest.py``)."""
import harness


def read(ctx):
    return harness.metric_reader("campaign_busy.ingest").read(ctx)
