"""Device-idle time inside ``FleetServer.ingest``'s chunks
(``serve.ingest``), over the traced window: the idle share the server's own
code holds the device back by, at most ``device_idle.ingest``.  Read from
the program's own spans (``bench/program_spans.py``)."""
import program_spans


def read(ctx):
    return program_spans.share("idle_s", ("serve.ingest",), "serve.ingest")
