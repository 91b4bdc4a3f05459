"""Device-busy time inside the profiling sweeps of a tick
(``serve.tick.hit_sweep``, the sweep at the generations' rows, and
``serve.tick.every_row``, the conventional DIMMs' every-row sweep), over
the traced window; read from the program's own spans
(``bench/program_spans.py``)."""
import program_spans


def read(ctx):
    return program_spans.share(
        "busy_s", ("serve.tick.hit_sweep", "serve.tick.every_row"),
        "serve.tick")
