"""Generations founded VERIFIED although their test rows show one arm of
the open-bitline V only, over the whole run, set-up included: the
``region="one_arm", verified="true"`` series of
``repro_serve_found_total`` in the process's metrics registry, read after
the window.  Reads 0 where the founding rule holds; None for a program
without that counter."""


def read(ctx):
    from repro.obs import REGISTRY
    family = REGISTRY.snapshot().get("repro_serve_found_total")
    if family is None:
        return None
    return sum(s["value"] for s in family["series"]
               if s["labels"].get("region") == "one_arm"
               and s["labels"].get("verified") == "true")
