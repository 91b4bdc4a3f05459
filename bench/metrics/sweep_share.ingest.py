"""Share of the traced window taken by the profiling sweeps: the served
sweep (``_profile_impl`` as the chunk program ``serve_profile``, jitted
under no name of its own: the unnamed program without the ``fail_prob``
kernel) and the every-row sweep (``jit__profile_impl``)."""


def _sweep(p):
    return p["name"] == "jit__profile_impl" or (
        p["name"] == "jit__unknown" and "fail_prob" not in p["kernels"])


def read(ctx):
    red = ctx.trace
    if not red or red["window_s"] <= 0:
        return None
    sec = sum(p["seconds"] for p in red["programs"] if _sweep(p))
    return sec / red["window_s"] if sec > 0 else None
