"""Device-busy time inside the profiling sweeps of ingest
(``serve.ingest.hit_sweep``, the served sweep of the chunk, and
``serve.ingest.every_row``, the conventional DIMMs' every-row sweep), over
the traced window; read from the program's own spans
(``bench/program_spans.py``)."""
import program_spans


def read(ctx):
    return program_spans.share(
        "busy_s", ("serve.ingest.hit_sweep", "serve.ingest.every_row"),
        "serve.ingest")
