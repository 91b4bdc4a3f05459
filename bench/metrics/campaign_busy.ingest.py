"""Device-busy time inside the campaign telemetry phase
(``serve.ingest.campaign``: the three ``hash_poisson_counts`` calls of a
chunk), over the traced window; read from the program's own spans
(``bench/program_spans.py``)."""
import program_spans


def read(ctx):
    return program_spans.share("busy_s", ("serve.ingest.campaign",),
                               "serve.ingest")
