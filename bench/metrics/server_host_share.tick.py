"""Device-idle time inside ``FleetServer.tick`` (``serve.tick``, the heap
drain included), over the traced window: the idle share the server's own
code holds the device back by, at most ``device_idle.tick``.  Read from
the program's own spans (``bench/program_spans.py``)."""
import program_spans


def read(ctx):
    return program_spans.share("idle_s", ("serve.tick",), "serve.tick")
