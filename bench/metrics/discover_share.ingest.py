"""Wall time of discovery (``serve.ingest.discover``: recovery, voting and
the vulnerable rows of the generations a chunk founds), over the traced
window; 0 in a window where no chunk ran discovery.  Read from the
program's own spans (``bench/program_spans.py``)."""
import program_spans


def read(ctx):
    return program_spans.share("seconds", ("serve.ingest.discover",),
                               "serve.ingest")
