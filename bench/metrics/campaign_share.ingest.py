"""Share of the traced window taken by the campaign telemetry program
(``streaming._campaign_impl`` through ``hash_poisson_counts``).  The chunk
programs are jitted under no name of their own (``jit__unknown``); it is the
unnamed program that runs the ``fail_prob`` kernel."""


def read(ctx):
    red = ctx.trace
    if not red or red["window_s"] <= 0:
        return None
    sec = sum(p["seconds"] for p in red["programs"]
              if p["name"] == "jit__unknown" and "fail_prob" in p["kernels"])
    return sec / red["window_s"] if sec > 0 else None
