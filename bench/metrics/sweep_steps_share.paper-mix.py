"""In the paper-mix cell, the share of the timing-grid points the
profiling sweeps evaluated over the whole run, read as
``sweep_steps_share.ingest`` reads it
(``bench/metrics/sweep_steps_share.ingest.py``)."""
import harness


def read(ctx):
    return harness.metric_reader("sweep_steps_share.ingest").read(ctx)
