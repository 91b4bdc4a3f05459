"""Share of the traced window in which no operation ran on the device:
1 - busy / window, from the profiler trace (``bench/trace_reduce.py``)."""


def read(ctx):
    red = ctx.trace
    if not red or red["window_s"] <= 0:
        return None
    return 1.0 - red["busy_s"] / red["window_s"]
