"""Device-busy time inside ingest's every-row sweep
(``serve.ingest.every_row``, the conventional DIMMs of a chunk), over the
traced window; 0 in a window with no conventional DIMM.  Read from the
program's own spans (``bench/program_spans.py``)."""
import program_spans


def read(ctx):
    return program_spans.share("busy_s", ("serve.ingest.every_row",),
                               "serve.ingest")
