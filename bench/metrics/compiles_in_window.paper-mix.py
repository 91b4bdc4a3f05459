"""In the paper-mix cell, the programs built inside the measured window,
read as ``compiles_in_window.ingest`` reads it
(``bench/metrics/compiles_in_window.ingest.py``)."""
import harness


def read(ctx):
    return harness.metric_reader("compiles_in_window.ingest").read(ctx)
