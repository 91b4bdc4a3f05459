"""What the fleet loops (``ingest``, ``tick``) share: building the
server from a configuration file, the input pin, and the comparison of
served tables with the plain reference (``reference/fleet.py``)."""
from __future__ import annotations

import numpy as np

from harness import BenchError, sha256_arrays, log
from reference import fleet as fleet_ref


def geometry(config: dict):
    from repro.core.geometry import DimmGeometry
    return DimmGeometry(**config["geometry"])


def fleet(config: dict, n: int, seed: int):
    """The configuration's synthetic fleet of ``n`` DIMMs from ``seed``."""
    from repro.core.population import synthetic_fleet
    return synthetic_fleet(n, geometry(config), seed=seed % 2 ** 32)


def arrivals(config: dict, traffic: dict, n: int) -> np.ndarray:
    """The first ``n`` serials, in serial order, of the designs that the
    traffic lets arrive (``traffic["designs"]``, names from the
    configuration's ``designs``: the fleet's design is serial modulo their
    number)."""
    names = config["designs"]
    keep = [names.index(d) for d in traffic["designs"]]
    span = -(-n * len(names) // len(keep)) + len(names)
    s = np.arange(span, dtype=np.int64)
    return s[np.isin(s % len(names), keep)][:n]


def leaves(batch) -> dict:
    return {k: np.asarray(getattr(batch, k)) for k in fleet_ref.LEAVES}


def fleet_digest(config: dict) -> str:
    """Digest of every leaf of chunk 0 of the fleet of seed 0."""
    from repro.core.substrate import _LEAVES
    pin = config["pins"]["fleet"]
    b = fleet(config, pin["dimms"], 0).chunk(0, pin["dimms"])
    return sha256_arrays({k: np.asarray(getattr(b, k)) for k in _LEAVES})


def check_pins(config: dict) -> None:
    """Stop before any work where the program no longer generates the
    pinned fleet, or the server's defaults moved from the file's."""
    import dataclasses

    from repro.core import geometry as geo
    from repro.serve import FleetConfig
    got = fleet_digest(config)
    want = config["pins"]["fleet"]["sha256"]
    if got != want:
        raise BenchError(
            f"synthetic_fleet changed: chunk 0 of seed 0 digests {got}, the "
            f"configuration pins {want}")
    from repro.core.population import fleet_templates
    designs = [f"{t.name}-{t.die}" for t in fleet_templates(geometry(config))]
    if designs != config["designs"]:
        raise BenchError(f"fleet_templates changed: the fleet cycles "
                         f"{designs}, the configuration states "
                         f"{config['designs']}")
    named = getattr(geo, config["pins"]["geometry"])
    if dataclasses.asdict(named) != dataclasses.asdict(geometry(config)):
        raise BenchError(f"geometry.{config['pins']['geometry']} no longer "
                         f"matches the configuration's geometry")
    defaults = dataclasses.asdict(FleetConfig())
    for k, v in config["fleet_config"].items():
        if k not in defaults:
            raise BenchError(f"FleetConfig has no field {k!r}")
        if isinstance(defaults[k], tuple):
            v = tuple(v)
        if k not in config.get("reduced", {}) and defaults[k] != v:
            raise BenchError(f"FleetConfig.{k} default moved: {defaults[k]!r}"
                             f", the configuration states {v!r}")


def arrival_stream(base, order: np.ndarray):
    """A stream whose position ``p`` holds the DIMM of serial ``order[p]``
    of ``base`` (the serial travels with its DIMM).  ``order`` is read at
    every chunk, so a loop may reorder positions not yet ingested."""
    from repro.core.streaming import PopulationStream
    from repro.serve import take_batch

    def chunk_fn(lo, hi):
        s = order[lo:hi]
        a = int(s.min())
        return take_batch(base.chunk(a, int(s.max()) + 1), s - a)
    return PopulationStream(len(order), base.geom, chunk_fn)


def server(config: dict, source):
    from repro.serve import FleetConfig, FleetServer
    fc = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config["fleet_config"].items()}
    return FleetServer(source, FleetConfig(**fc))


def served(srv, serials: np.ndarray) -> dict:
    """Served records of ``serials``; ``missing`` counts the unserved."""
    idx = srv.state.index
    known = np.asarray([int(s) in idx for s in serials], bool)
    rows = srv.state.rows_for(serials[known]) if known.any() \
        else np.zeros(0, np.int64)
    view = srv.state.view
    return {"serial": serials[known], "missing": int((~known).sum()),
            "table": np.array(view("table")[rows], np.float32),
            "path": np.array(view("path")[rows]),
            "profiled_at": np.array(view("profiled_at")[rows], np.float32)}


def reference_tables(config: dict, base, serials: np.ndarray, *, every_row:
                     bool, age_years: float, dtype) -> np.ndarray:
    """The plain reference's tables of ``serials`` of the fleet ``base``."""
    import jax.numpy as jnp
    if len(serials) == 0:
        return np.zeros((0, 4), np.float32)
    from repro.serve import take_batch
    g = geometry(config)
    fc = config["fleet_config"]
    lo, hi = int(serials.min()), int(serials.max()) + 1
    batch = take_batch(base.chunk(lo, hi), serials - lo)
    rows = np.arange(g.rows_per_mat) if every_row \
        else fleet_ref.design_worst_rows(g.rows_per_mat)
    return fleet_ref.tables(
        leaves(batch), rows, age_years=age_years,
        temp_C=fc["profile_temp_C"], refresh_ms=fc["profile_refresh_ms"],
        guard_cycles=fc["guard_cycles"], multibit=fc["multibit_only"],
        cols=g.cols_per_mat, mats=g.mats_x, chips=g.chips,
        block=config["reference"]["block_all" if every_row else
                                  "block_worst"],
        dtype=getattr(jnp, dtype))


def compare(config: dict, base, rec: dict, age_years: float,
            control: str | None = None) -> dict:
    """Compare every served table in ``rec`` with the reference's at
    ``age_years``, on the path the server reports for its DIMM (the
    design-worst rows, or every row for the every-row sweep); returns
    mismatch counts.  With ``control`` (a dtype name) the reference
    computed in that precision takes the served tables' place."""
    from repro.serve.state import PATH_CONVENTIONAL
    every_row = rec["path"] == PATH_CONVENTIONAL
    out = {"compared": len(rec["serial"]), "mismatch": 0}
    for kind, every in (("worst", False), ("all", True)):
        idx = np.flatnonzero(every_row == every)
        kw = dict(every_row=every, age_years=age_years)
        ref = reference_tables(config, base, rec["serial"][idx],
                               dtype="float32", **kw)
        got = rec["table"][idx] if control is None else reference_tables(
            config, base, rec["serial"][idx], dtype=control, **kw)
        bad = int((ref != got).any(axis=1).sum())
        out["mismatch"] += bad
        out[f"mismatch_{kind}"] = bad
    return out


def check_numbers(config: dict, traffic: dict, base, snaps: list,
                  control: str | None = None) -> dict:
    """The numbers that decide ``correct`` for the served tables in
    ``snaps`` (each: the records of the DIMMs served at one fleet age):
    DIMMs never served, DIMMs not profiled at the age they should have
    been, and the share of served tables that differ from the plain
    reference."""
    missing = stale = compared = mismatch = 0
    for rec, age in snaps:
        missing += rec["missing"]
        stale += int((rec["profiled_at"] != np.float32(age)).sum())
        res = compare(config, base, rec, age, control)
        log(f"{control or 'served'} vs reference at age {age:g}y: {res}")
        compared += res["compared"]
        mismatch += res["mismatch"]
    if compared == 0:
        raise BenchError("no served table to compare")
    return {"unserved": {"value": missing, "limit": 0},
            "misaged": {"value": stale, "limit": 0},
            "table_mismatch_share": {"value": mismatch / compared,
                                     "limit": traffic["check"]
                                     ["mismatch_share_limit"]}}
