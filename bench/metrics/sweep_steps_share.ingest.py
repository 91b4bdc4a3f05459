"""Share of the timing-grid points the profiling sweeps evaluated, over the
whole run, set-up included: the ``outcome="evaluated"`` series of
``repro_sweep_grid_steps_total`` over all its series (every entry point
and parameter), in the process's metrics registry, read after the window.
A sweep ends once every DIMM of its batch has stopped, so this reads 1
only where every call walks every grid to its end.  None for a program
without that counter."""


def read(ctx):
    from repro.obs import REGISTRY
    family = REGISTRY.snapshot().get("repro_sweep_grid_steps_total")
    if family is None:
        return None
    steps = {"evaluated": 0.0, "skipped": 0.0}
    for s in family["series"]:
        steps[s["labels"]["outcome"]] += s["value"]
    total = steps["evaluated"] + steps["skipped"]
    return steps["evaluated"] / total if total else None
