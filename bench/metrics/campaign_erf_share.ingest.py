"""Share of the campaign kernel's failure terms that it evaluated, over the
whole run, set-up included: the ``outcome="evaluated"`` series of
``repro_campaign_erf_evals_total`` over all its series (the campaign and
discovery's λ), in the process's metrics registry, read after the window.
The row-sum ``fail_prob`` kernel skips a (row block, column strip, mat)
tile's term where it is exactly 0 or 1, so this reads 1 only where no term
saturates.  None for a program without that counter."""


def read(ctx):
    from repro.obs import REGISTRY
    family = REGISTRY.snapshot().get("repro_campaign_erf_evals_total")
    if family is None:
        return None
    terms = {"evaluated": 0.0, "saturated": 0.0}
    for s in family["series"]:
        terms[s["labels"]["outcome"]] += s["value"]
    total = terms["evaluated"] + terms["saturated"]
    return terms["evaluated"] / total if total else None
