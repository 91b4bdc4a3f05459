"""``FleetServer``: the paper's online DIVA Profiling as a fleet service.

Seven PRs of batch machinery turned into a long-lived server: DIMMs arrive
as streaming telemetry chunks (``core/streaming``), get a timing table by
the cheapest path their signature allows, and stay fresh through a
staleness-driven re-profiling queue — all through the one-compiled-program
chunk substrate, so serving a million-DIMM fleet costs the same set of XLA
programs as serving sixty-four.

Serving paths, cheapest first:

  * HIT — the DIMM's campaign signature cosine-matches a cached generation
    (``serve.state.GenerationCache``): its table comes from a K-row sweep at
    the generation's cached external test addresses.  Because the profiling
    hash never keys on the test region, a hit whose cached addresses decode
    to the design-worst internal rows reproduces the geometry-oracle
    ``diva_profile`` table bit for bit — the bench's parity gate.
  * DISCOVER — the signature founds a new generation: scramble recovery is
    pooled over the founding members (votes from every informative (point,
    member, subarray) recovery), the vulnerable rows are read off the
    generation's onset-point canonical profile, and the resulting external
    addresses are cached so every LATER member of the generation hits.
  * CONVENTIONAL — no usable signature (zero errors at every campaign
    point), or a signature matching an UNVERIFIED generation (one whose
    founding vote pool was too small or too incoherent to trust the
    discovered region, or whose region shows one arm of the open-bitline
    V only): the safe every-row sweep.

Staleness: a table profiled with ``guard_cycles`` cycles of margin stays
safe until aging drift (``aging_coef`` ns/year — the lifetime model's
adder) consumes the guard band, so each DIMM's re-profile deadline is
``profiled_at + guard / aging_coef`` (clamped).  ``tick(now)`` drains the
deadline heap and re-profiles due DIMMs in chunked sweeps at their cached
regions under the aged operating condition.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.core.latency import DEFAULT_ITERS, DEFAULT_PATTERNS
from repro.core.streaming import as_stream, hash_poisson_counts, pad_batch
from repro.core.substrate import (_LEAVES, _chunk_jitted, _pad0,
                                  _profile_impl, lifetime_adders,
                                  pattern_stress, profile_population_arrays,
                                  row_error_lambda, sweep_steps)
from repro.core.timing import CYCLE_NS, PARAMS
from repro.discovery.generation import covers_both_arms, vulnerable_rows
from repro.discovery.recover import (mapping_tables,
                                     recover_mapping_population, vote_mapping)
from repro.discovery.signatures import (bit_signature_population,
                                        signature_features)
from repro.obs import REGISTRY as _OBS_REGISTRY
from repro.obs import span as _span
from repro.serve.state import (PATH_CONVENTIONAL, PATH_DISCOVER, PATH_HIT,
                               FleetState, GenerationCache)

# Serving-layer metrics (obs layer, ARCHITECTURE 3h).  Every series is
# labeled with a process-unique server id so several FleetServers in one
# process (tests, checkpoint roundtrips) never mix counts; each server holds
# its bound children — no label resolution on the serving path.
_SERVER_IDS = itertools.count()
_PATH_NAMES = {PATH_HIT: "hit", PATH_DISCOVER: "discover",
               PATH_CONVENTIONAL: "conventional"}
_M_INGEST = _OBS_REGISTRY.counter(
    "repro_serve_ingest_total", "DIMMs ingested by serving path",
    labelnames=("server", "path"))
_M_QUERIES = _OBS_REGISTRY.counter(
    "repro_serve_queries_total", "timing-table queries served",
    labelnames=("server",))
_M_QLAT = _OBS_REGISTRY.histogram(
    "repro_serve_query_latency_seconds", "table query latency",
    labelnames=("server",))
_M_AGE = _OBS_REGISTRY.gauge(
    "repro_serve_max_table_age_years",
    "worst served-table age at the last staleness() call",
    labelnames=("server",))
_M_GENS = _OBS_REGISTRY.gauge(
    "repro_serve_generations", "generations in the signature cache",
    labelnames=("server",))
_M_REPROF = _OBS_REGISTRY.counter(
    "repro_serve_reprofiled_total", "DIMMs re-profiled by tick()",
    labelnames=("server",))
# One count per founded generation: region "both_arms" where its test rows
# show both arms of the open-bitline V (generation.covers_both_arms), else
# "one_arm"; a one-arm founding is never verified.
_REGIONS = ("both_arms", "one_arm")
_M_FOUND = _OBS_REGISTRY.counter(
    "repro_serve_found_total",
    "generations founded, by verification and test-region shape",
    labelnames=("server", "verified", "region"))
# One span per phase of an ingest chunk or a tick (never per DIMM); a child
# phase's name starts with its parent's, and every phase span observes its
# wall time here, so per-phase tails survive untraced runs.
_M_PHASE = _OBS_REGISTRY.histogram(
    "repro_serve_phase_seconds",
    "wall time of one FleetServer phase (the span of that name)",
    labelnames=("server", "phase"))


def oracle_parity(server: "FleetServer", n_prefix: int) -> dict:
    """Compare the first ``n_prefix`` served tables against the dense oracle
    for their path: hit/discover vs ``diva_profile``'s sweep
    (region="worst"), conventional vs the every-row sweep (region="all").
    Each DIMM is profiled at the fleet age its table was served at
    (``profiled_at``), so the check holds after re-profiling ticks too.
    Tables must match bit for bit."""
    from repro.core.substrate import profile_population_arrays
    view = server.state.view
    n_prefix = min(int(n_prefix), len(view("serial")))
    batch = server.stream.chunk(0, n_prefix)
    aged = dataclasses.replace(
        batch, age_years=np.asarray(view("profiled_at")[:n_prefix],
                                    np.float32))
    kw = dict(temp_C=server.cfg.profile_temp_C,
              refresh_ms=server.cfg.profile_refresh_ms,
              guard_cycles=server.cfg.guard_cycles,
              multibit_only=server.cfg.multibit_only)
    diva = np.asarray(profile_population_arrays(aged, region="worst", **kw),
                      np.float32)[:, :4]
    conv = np.asarray(profile_population_arrays(aged, region="all", **kw),
                      np.float32)[:, :4]
    is_conv = view("path")[:n_prefix] == PATH_CONVENTIONAL
    oracle = np.where(is_conv[:, None], conv, diva)
    ok = (view("table")[:n_prefix] == oracle).all(axis=1)
    return {"n_prefix": n_prefix, "n_mismatch": int((~ok).sum()),
            "parity": bool(ok.all())}


def take_batch(batch, idx):
    """Arbitrary-index population subset (the fancy-index sibling of
    ``streaming.slice_batch``)."""
    idx = np.asarray(idx)
    return dataclasses.replace(
        batch, **{n: np.asarray(getattr(batch, n))[idx] for n in _LEAVES})


def concat_batches(parts):
    if len(parts) == 1:
        return parts[0]
    return dataclasses.replace(
        parts[0], **{n: np.concatenate([np.asarray(getattr(p, n))
                                        for p in parts]) for n in _LEAVES})


@dataclass(frozen=True)
class FleetConfig:
    """Operating points and policies of one fleet server."""
    chunk_size: int = 512
    # generation matching: campaign telemetry -> onset-block signatures
    threshold: float = 0.85
    k_rows: int = 2
    campaign_param: str = "trp"
    campaign_t_ops: tuple = (10.0, 7.5, 5.0)
    campaign_temp_C: float = 85.0
    campaign_refresh_ms: float = 256.0
    campaign_seed: int = 0
    onset_min_count: float = 1024.0
    # generation verification: a discovered region is trusted for future
    # hits only when the founding vote pool was large enough and agreed
    # strongly enough on one scramble, and its rows show both arms of the
    # open-bitline V (see _found)
    consensus_min_share: float = 0.55
    min_founders: int = 4
    # the served operating point (diva_profile defaults)
    profile_temp_C: float = 55.0
    profile_refresh_ms: float = 64.0
    guard_cycles: int = 1
    multibit_only: bool = True
    # staleness: horizon_years = clamp(safety * guard_ns / aging_coef)
    stale_safety: float = 1.0
    horizon_min_years: float = 0.25
    horizon_max_years: float = 10.0


class FleetServer:
    """Online timing-table service over one ``PopulationStream``.

    ``ingest`` registers the next DIMMs of the stream (chunks in serial
    order — the clusterer's contract), ``query``/``query_batch`` serve
    tables, ``tick`` re-profiles due DIMMs, ``save``/``load`` checkpoint the
    whole serving state (generation cache included) so a restarted server
    resumes mid-ingest with identical labels, tables, and deadlines.
    """

    def __init__(self, source, config: FleetConfig = FleetConfig(), *,
                 checkpoint_dir: str | None = None, keep: int = 3):
        self.stream = as_stream(source)
        self.cfg = config
        self.cache = GenerationCache(threshold=config.threshold)
        self.state = FleetState()
        self._heap: list[tuple[float, int]] = []
        self._ingested = 0          # stream serials [0, _ingested) are live
        self.clock = 0.0            # fleet age (years) of the last ingest/tick
        self.ckpt = None
        if checkpoint_dir is not None:
            from repro.checkpoint.manager import CheckpointManager
            self.ckpt = CheckpointManager(checkpoint_dir, keep=keep)
        g = self.stream.geom
        self.founding_stats: dict[int, dict] = {}
        self._full = int(config.chunk_size)
        self._stress = jnp.asarray(pattern_stress(DEFAULT_PATTERNS))
        self._statics = dict(guard_cycles=config.guard_cycles,
                             iters=DEFAULT_ITERS,
                             multibit=config.multibit_only, banks=1,
                             axes=PARAMS, retention=False)
        self._nbits = int(np.log2(g.rows_per_mat))
        self._sid = str(next(_SERVER_IDS))
        self._m_path = {name: _M_INGEST.labels(server=self._sid, path=name)
                        for name in _PATH_NAMES.values()}
        self._m_queries = _M_QUERIES.labels(server=self._sid)
        self._m_qlat = _M_QLAT.labels(server=self._sid)
        self._m_age = _M_AGE.labels(server=self._sid)
        self._m_gens = _M_GENS.labels(server=self._sid)
        self._m_reprof = _M_REPROF.labels(server=self._sid)
        self._m_found = {(v, reg): _M_FOUND.labels(
            server=self._sid, verified=str(v).lower(), region=reg)
            for v in (True, False) for reg in _REGIONS}
        self._m_phase: dict = {}

    def _phase(self, name: str, **args):
        """The span of one serving phase, observed into
        ``repro_serve_phase_seconds{server, phase=name}``."""
        hist = self._m_phase.get(name)
        if hist is None:
            hist = self._m_phase[name] = _M_PHASE.labels(server=self._sid,
                                                         phase=name)
        return _span(name, hist, **args)

    # ------------------------------------------------------------- ingest

    def ingest(self, n: int | None = None, *, now: float | None = None
               ) -> dict:
        """Register the next ``n`` DIMMs of the stream (default: the rest).
        Returns per-path counts for the ingested span."""
        now = self.clock if now is None else float(now)
        lo0 = self._ingested
        hi0 = self.stream.n_dimms if n is None else min(lo0 + int(n),
                                                        self.stream.n_dimms)
        before = (self.cache.hits, self.cache.misses, self.cache.conventional)
        for lo in range(lo0, hi0, self._full):
            hi = min(lo + self._full, hi0)
            with self._phase("serve.ingest", server=self._sid, lo=lo, hi=hi):
                self._ingest_chunk(self.stream.chunk(lo, hi), now)
            self._ingested = hi
        self.clock = max(self.clock, now)
        return {"ingested": hi0 - lo0,
                "hits": self.cache.hits - before[0],
                "misses": self.cache.misses - before[1],
                "conventional": self.cache.conventional - before[2],
                "n_generations": self.cache.n_generations}

    def _ingest_chunk(self, batch, now: float) -> None:
        cfg = self.cfg
        n = batch.n_dimms

        # campaign telemetry: serial-keyed counts at every operating point
        # (one compiled program; t_op is data, not a static)
        with self._phase("serve.ingest.campaign"):
            padded = pad_batch(batch, self._full - n)
            counts_t = np.stack([
                hash_poisson_counts(padded, cfg.campaign_param, float(t),
                                    temp_C=cfg.campaign_temp_C,
                                    refresh_ms=cfg.campaign_refresh_ms,
                                    seed=cfg.campaign_seed)[:n]
                for t in cfg.campaign_t_ops])              # (T, n, S, R)
        T = counts_t.shape[0]

        # per-DIMM onset point + onset-block signature features (the
        # BlindDiva matching key: DIMMs with different onsets are different
        # designs and land in disjoint feature blocks)
        with self._phase("serve.ingest.signature"):
            max_t = np.stack([np.median(counts_t[t].max(axis=2), axis=1)
                              for t in range(T)])          # (T, n)
            onset = np.full(n, T - 1, np.int64)
            for d in range(n):
                hit = np.flatnonzero(max_t[:, d] >= cfg.onset_min_count)
                if hit.size:
                    onset[d] = int(hit[0])
            feats_t = [signature_features(
                bit_signature_population(counts_t[t].astype(np.int32)))
                for t in range(T)]                         # T x (n, nbits)
            nb = self._nbits
            feats = np.zeros((n, T * nb))
            for d in range(n):
                t = onset[d]
                feats[d, t * nb:(t + 1) * nb] = feats_t[t][d]

        with self._phase("serve.ingest.match"):
            labels = self.cache.match(feats)               # (n,) provisional

        # paths: hit = label with a VERIFIED cached region; new labels found
        # generations (verification happens at founding — see _discover).
        # Members of an unverified generation keep the label for cluster
        # accounting but take the safe conventional sweep.
        genuine = max_t[onset, np.arange(n)] >= cfg.onset_min_count
        new_gens = sorted({int(l) for l in labels
                           if l >= 0 and not self.cache.known(l)})
        if new_gens:
            with self._phase("serve.ingest.discover",
                             n_generations=len(new_gens)):
                self._discover(batch, counts_t, onset, labels, new_gens,
                               genuine)
        with self._phase("serve.ingest.route"):
            ver = np.asarray([l >= 0 and self.cache.verified(int(l))
                              for l in labels])
            path = np.where(~ver, PATH_CONVENTIONAL,
                            np.where(np.isin(labels, new_gens),
                                     PATH_DISCOVER, PATH_HIT)).astype(np.int8)
            conv = path == PATH_CONVENTIONAL
            self.cache.hits += int((path == PATH_HIT).sum())
            self.cache.misses += int((path == PATH_DISCOVER).sum())
            self.cache.conventional += int(conv.sum())
            for code, name in _PATH_NAMES.items():
                self._m_path[name].inc(int((path == code).sum()))
            self._m_gens.set(self.cache.n_generations)
            internal = self._internal_rows(batch, labels, conv)

        # one restricted sweep for every DIMM with a verified region (hit +
        # fresh discoveries); conventional DIMMs take the every-row sweep
        tables = self._sweep(batch, internal, conv, now, "serve.ingest")

        with self._phase("serve.ingest.commit"):
            horizon = self._horizon_years(batch)
            due = now + horizon
            serials = np.asarray(batch.serial, np.int64)
            self.state.append(serials, tables, labels, path,
                              np.full(n, now, np.float32), due, horizon)
            for s, t in zip(serials, due):
                heapq.heappush(self._heap, (float(t), int(s)))

    # ----------------------------------------------------- discovery (miss)

    def _discover(self, batch, counts_t, onset, labels, new_gens,
                  genuine) -> None:
        """Found new generations from this chunk's unmatched members: pooled
        scramble recovery -> onset canonical profile -> vulnerable rows ->
        cached external test addresses.  A generation is cached VERIFIED
        only when the founding pool is big enough (``min_founders``) and its
        votes agree strongly enough on one scramble
        (``consensus_min_share``) — otherwise the label survives for
        cluster accounting but members take the conventional sweep."""
        cfg = self.cfg
        g = batch.geom
        S, R = g.subarrays, g.rows_per_mat
        idx = np.flatnonzero(np.isin(labels, new_gens))
        m = len(idx)
        pad = self._full - m
        sub = take_batch(batch, idx)
        padded_sub = pad_batch(sub, pad)
        sub_counts = counts_t[:, idx]                      # (T, m, S, R)

        # per-point recovery on the clone-padded subset: every founding in
        # the fleet's lifetime reuses ONE compiled recovery program
        rec_t = []
        with self._phase("serve.ingest.discover.recover"):
            for t, t_op in enumerate(cfg.campaign_t_ops):
                lam = row_error_lambda(
                    padded_sub, cfg.campaign_param, float(t_op),
                    temp_C=cfg.campaign_temp_C,
                    refresh_ms=cfg.campaign_refresh_ms,
                    internal_order=True).reshape(self._full, S, R)
                rec_t.append(recover_mapping_population(
                    _pad0(sub_counts[t], pad).astype(np.int64), lam))
        has_signal = sub_counts.max(axis=3) > 0            # (T, m, S)

        for gen in new_gens:
            self._found(gen, idx, labels, onset, sub_counts, rec_t,
                        has_signal, genuine)

    def _found(self, gen, idx, labels, onset, sub_counts, rec_t, has_signal,
               genuine) -> None:
        """Vote one new generation's scramble from its members' recoveries
        (span ``serve.ingest.discover.vote``), then read its vulnerable rows
        off the onset-point canonical profile and cache them, VERIFIED or
        not (span ``serve.ingest.discover.region``).  Verification needs a
        large enough founding pool that agrees on one scramble, and test
        rows that show both arms of the open-bitline V
        (``covers_both_arms``): a one-arm region is never trusted."""
        cfg = self.cfg
        R = sub_counts.shape[3]
        T = sub_counts.shape[0]
        nb = self._nbits
        pos = np.flatnonzero(labels[idx] == gen)       # positions in sub
        with self._phase("serve.ingest.discover.vote"):
            vb, vx, vc = [], [], []
            for t in range(T):
                keep = has_signal[t][pos].reshape(-1)
                if not keep.any():
                    continue
                vb.append(rec_t[t]["ext_bit"][pos].reshape(-1, nb)[keep])
                vx.append(rec_t[t]["xor"][pos].reshape(-1, nb)[keep])
                vc.append(rec_t[t]["confidence"][pos].reshape(-1, nb)[keep])
            if not vb:                                 # nothing observed
                vb = [rec_t[-1]["ext_bit"][pos[0]]]
                vx = [rec_t[-1]["xor"][pos[0]]]
                vc = [rec_t[-1]["confidence"][pos[0]]]
            vb, vx, vc = (np.concatenate(v) for v in (vb, vx, vc))
            founder = int(pos[0])
            t_on = int(onset[idx[founder]])
            b, x = vote_mapping(vb, vx, vc,
                                rec_t[t_on]["order_int"][founder, 0])
            # consensus quality: confidence-weighted fraction of the vote
            # pool that agrees with the voted scramble, per internal bit.
            # A real generation's members vote coherently (share >~ 0.6);
            # a cluster of weak-die noise scatters (share <~ 0.5) — and a
            # tiny pool can be wrong while fully self-consistent, so small
            # foundings are never trusted regardless of share.
            agree = (vb == b[None, :]) & (vx == x[None, :])  # (K, nbits)
            wsum = np.maximum(vc.sum(axis=0), 1e-9)
            share = (vc * agree).sum(axis=0) / wsum        # per int bit
        with self._phase("serve.ingest.discover.region"):
            est, i2e = mapping_tables(b, x, R)         # consensus map
            # generation canonical profile at the onset point, scattered
            # back through the consensus mapping
            summed = sub_counts[t_on, pos].sum(axis=(0, 1))  # (R,) external
            prof = np.zeros(R, np.int64)
            np.add.at(prof, est, summed)
            vuln = vulnerable_rows(prof, cfg.k_rows)
            mass = float(prof[vuln].sum()) / float(max(prof.sum(), 1))
            region = ("both_arms" if covers_both_arms(prof, vuln)
                      else "one_arm")
            verified = (float(share.mean()) >= cfg.consensus_min_share
                        and len(pos) >= cfg.min_founders
                        and region == "both_arms")
            self.founding_stats[int(gen)] = {
                "n_founders": int(len(pos)), "region_mass": mass,
                "conf_mean": float(vc.mean()),
                "share_mean": float(share.mean()),
                "share_min": float(share.min()),
                "all_genuine": bool(genuine[idx[pos]].all()),
                "region": region, "verified": verified}
            self.cache.install(gen, i2e[vuln], verified=verified)
            self._m_found[verified, region].inc()

    # --------------------------------------------------------- profiling

    def _internal_rows(self, batch, labels, conv) -> np.ndarray:
        """(C, k_rows) internal test rows: each DIMM's generation region
        (cached external rows) through its own scramble; conventional DIMMs
        keep zeros (their sweep covers every row)."""
        e2i = np.asarray(batch.ext_to_int, np.int64)
        internal = np.zeros((len(labels), self.cfg.k_rows), np.int64)
        for d in range(len(labels)):
            if not conv[d]:
                internal[d] = e2i[d][self.cache.ext_rows(labels[d])]
        return internal

    def _sweep(self, batch, internal_rows, conv, now: float,
               prefix: str) -> np.ndarray:
        """(C, 4) tables: one restricted sweep of the chunk at its internal
        rows, then the every-row sweep of its conventional DIMMs."""
        tables = self._profile_rows(batch, internal_rows, now, prefix)
        if conv.any():
            sub = take_batch(batch, np.flatnonzero(conv))
            tables[conv] = self._profile_all_rows(sub, now, prefix)
        return tables

    def _profile_rows(self, batch, internal_rows, now: float,
                      prefix: str = "serve.ingest") -> np.ndarray:
        """(C, 4) tables at per-DIMM internal regions through the one
        compiled serve program (clone-padded chunk, donated buffers), under
        the span ``<prefix>.hit_sweep``."""
        n = batch.n_dimms
        with self._phase(prefix + ".hit_sweep", n=n):
            pad = self._full - n
            padded = pad_batch(batch, pad)
            rows = _pad0(np.asarray(internal_rows, np.int32), pad)
            adder = self._adder(padded, now)
            out, steps = _chunk_jitted(
                "serve_profile", _profile_impl, self._statics,
                donate=(0, 3))(padded, jnp.asarray(rows), self._stress,
                               jnp.asarray(adder))
            tables = np.array(out, np.float32)[:n, 0]
            sweep_steps("serve_profile", steps)
            return tables

    def _profile_all_rows(self, batch, now: float,
                          prefix: str = "serve.ingest") -> np.ndarray:
        """Conventional every-row sweep for the signatureless fallback,
        under the span ``<prefix>.every_row``."""
        cfg = self.cfg
        with self._phase(prefix + ".every_row", n=batch.n_dimms):
            aged = dataclasses.replace(
                batch, age_years=np.full(batch.n_dimms, now, np.float32))
            return np.asarray(profile_population_arrays(
                aged, region="all", temp_C=cfg.profile_temp_C,
                refresh_ms=cfg.profile_refresh_ms,
                guard_cycles=cfg.guard_cycles,
                multibit_only=cfg.multibit_only), np.float32)[:, :4]

    def _adder(self, batch, now: float) -> np.ndarray:
        """The aged operating-condition adder: ``condition_adders`` with the
        fleet clock overriding the batch's static age (bit-identical op
        order via ``lifetime_adders``)."""
        cfg = self.cfg
        return lifetime_adders(batch, np.full(1, now, np.float32),
                               np.full(1, cfg.profile_temp_C),
                               cfg.profile_refresh_ms)[0]

    def _horizon_years(self, batch) -> np.ndarray:
        cfg = self.cfg
        guard_ns = cfg.stale_safety * cfg.guard_cycles * CYCLE_NS
        ac = np.maximum(np.asarray(batch.aging_coef, np.float32), 1e-6)
        return np.clip(guard_ns / ac, cfg.horizon_min_years,
                       cfg.horizon_max_years).astype(np.float32)

    # ------------------------------------------------------------ queries

    def query(self, serial: int) -> dict:
        """One DIMM's serving record; KeyError for unknown serials."""
        if int(serial) not in self.state.index:
            raise KeyError(f"serial {int(serial)} not registered")
        with _span("serve.query", self._m_qlat, server=self._sid):
            i = self.state.index[int(serial)]
            out = {"serial": int(serial),
                   "table": self.state.view("table")[i].copy(),
                   "label": int(self.state.view("label")[i]),
                   "path": int(self.state.view("path")[i]),
                   "profiled_at": float(self.state.view("profiled_at")[i]),
                   "due_at": float(self.state.view("due_at")[i])}
        self._m_queries.inc()
        return out

    def query_batch(self, serials) -> np.ndarray:
        """(Q, 4) timing tables for a batch of serials (one gather)."""
        with _span("serve.query_batch", self._m_qlat, server=self._sid):
            rows = self.state.rows_for(serials)
            out = self.state.view("table")[rows]
        self._m_queries.inc(len(rows))
        return out

    def staleness(self, now: float | None = None) -> dict:
        """Fleet staleness report at ``now`` (default: the server clock):
        the worst table age, the fleet's staleness bound (max horizon), and
        how many DIMMs are past their deadline."""
        now = self.clock if now is None else float(now)
        age = now - self.state.view("profiled_at")
        horizon = self.state.view("horizon")
        out = {"now": now,
               "max_staleness_years": float(age.max()) if len(age) else 0.0,
               "bound_years": float(horizon.max()) if len(horizon) else 0.0,
               "n_overdue": int((self.state.view("due_at") < now).sum())}
        self._m_age.set(out["max_staleness_years"])
        return out

    def metrics(self) -> dict:
        """This server's observability block, read off the obs registry:
        serving-path mix, query count + latency histogram summary, the
        staleness gauge (refreshed here), generation-cache hit rate, and the
        chunk-cache compile counts — the numbers ``serve_bench.py`` reports
        and cross-checks against its independently computed gate values."""
        self.staleness()                       # refresh the age gauge
        paths = {name: int(c.value()) for name, c in self._m_path.items()}
        matched = paths["hit"] + paths["discover"]
        total = matched + paths["conventional"]
        fam = _OBS_REGISTRY.get("repro_compile_programs_total")
        compiles = {lv[1]: int(child.value()) for lv, child in fam._series()
                    if lv and lv[0] == "chunk"}
        return {"server": self._sid,
                "paths": paths,
                "ingested": int(self._ingested),
                "hit_rate": paths["hit"] / total if total else 0.0,
                "generations": int(self.cache.n_generations),
                "queries": int(self._m_queries.value()),
                "query_latency_seconds": self._m_qlat.summary(),
                "max_table_age_years": float(self._m_age.value()),
                "reprofiled": int(self._m_reprof.value()),
                "chunk_compiles": compiles}

    # --------------------------------------------------------------- tick

    def tick(self, now: float) -> dict:
        """Advance the fleet clock and re-profile every DIMM whose deadline
        passed, in chunked sweeps at the cached regions under the aged
        condition.  Returns the re-profile count."""
        with self._phase("serve.tick", server=self._sid, now=now) as sp:
            due: list[int] = []
            with self._phase("serve.tick.drain"):
                while self._heap and self._heap[0][0] <= now:
                    _, s = heapq.heappop(self._heap)
                    # stale heap entries (superseded by a later re-profile)
                    # drop out
                    i = self.state.index.get(s)
                    if i is not None and self.state.view("due_at")[i] <= now:
                        due.append(s)
            sp.set(reprofiled=len(due))
            for lo in range(0, len(due), self._full):
                self._reprofile(np.asarray(due[lo:lo + self._full]), now)
            self._m_reprof.inc(len(due))
        self.clock = max(self.clock, now)
        return {"now": now, "reprofiled": len(due)}

    def _reprofile(self, serials: np.ndarray, now: float) -> None:
        with self._phase("serve.tick.gather", n=len(serials)):
            serials = np.sort(serials)
            runs = np.split(serials,
                            np.flatnonzero(np.diff(serials) != 1) + 1)
            batch = concat_batches([
                self.stream.chunk(int(r[0]), int(r[-1]) + 1) for r in runs])
            rows_idx = self.state.rows_for(serials)
            labels = self.state.view("label")[rows_idx]
            conv = self.state.view("path")[rows_idx] == PATH_CONVENTIONAL
            internal = self._internal_rows(batch, labels, conv)
        tables = self._sweep(batch, internal, conv, now, "serve.tick")
        with self._phase("serve.tick.commit"):
            due = now + self.state.view("horizon")[rows_idx]
            self.state.update_rows(rows_idx, tables, now, due)
            for s, t in zip(serials, due):
                heapq.heappush(self._heap, (float(t), int(s)))

    # --------------------------------------------------------- checkpoint

    # the fixed checkpoint key set: dict pytrees flatten in sorted-key
    # order, so these names + the saved meta shapes reconstruct the
    # example_state for a restore that knows nothing else
    _STATE_KEYS = ("cache_counters", "cache_ext_rows", "cache_leaders",
                   "cache_members", "cache_verified", "fleet_due_at",
                   "fleet_horizon", "fleet_label", "fleet_path",
                   "fleet_profiled_at", "fleet_serial", "fleet_table",
                   "server_meta")

    def state_dict(self) -> dict[str, np.ndarray]:
        out = {f"fleet_{k}": v for k, v in self.state.state_dict().items()}
        out.update({f"cache_{k}": v
                    for k, v in self.cache.state_dict().items()})
        out["server_meta"] = np.asarray([self._ingested, self.clock],
                                        np.float64)
        assert tuple(sorted(out)) == self._STATE_KEYS
        return out

    def load_state(self, state: dict[str, np.ndarray]) -> None:
        self.state.load_state(
            {k[len("fleet_"):]: v for k, v in state.items()
             if k.startswith("fleet_")})
        self.cache.load_state(
            {k[len("cache_"):]: v for k, v in state.items()
             if k.startswith("cache_")})
        meta = np.asarray(state["server_meta"], np.float64)
        self._ingested = int(meta[0])
        self.clock = float(meta[1])
        self._heap = [(float(t), int(s))
                      for t, s in zip(self.state.view("due_at"),
                                      self.state.view("serial"))]
        heapq.heapify(self._heap)

    def save(self, step: int):
        if self.ckpt is None:
            raise RuntimeError("FleetServer built without checkpoint_dir")
        return self.ckpt.save(step, self.state_dict())

    def load(self, step: int | None = None) -> dict:
        """Restore from the checkpoint directory WITHOUT an in-memory
        example: leaf shapes/dtypes come from the saved meta (the fixed
        ``_STATE_KEYS`` set flattens in sorted order, matching the saved
        leaf order by construction)."""
        if self.ckpt is None:
            raise RuntimeError("FleetServer built without checkpoint_dir")
        meta = self.ckpt.meta(step)
        if len(meta["leaves"]) != len(self._STATE_KEYS):
            raise ValueError(
                f"checkpoint has {len(meta['leaves'])} leaves; a fleet "
                f"state has {len(self._STATE_KEYS)}")
        example = {k: np.zeros(info["shape"], np.dtype(info["dtype"]))
                   for k, info in zip(self._STATE_KEYS, meta["leaves"])}
        state, info = self.ckpt.restore(example, step)
        self.load_state(state)
        return info
