"""Share of the window's ingested DIMMs served on the HIT path: the
window delta of ``repro_serve_ingest_total{path}``, hit over all."""


def read(ctx):
    paths = ctx.counters.get("paths")
    total = sum(paths.values()) if paths else 0
    return paths["hit"] / total if total else None
