"""Device-busy time inside a tick's every-row sweep
(``serve.tick.every_row``, the conventional DIMMs), over the traced
window; read from the program's own spans (``bench/program_spans.py``)."""
import program_spans


def read(ctx):
    return program_spans.share("busy_s", ("serve.tick.every_row",),
                               "serve.tick")
