"""Serving driver: batched prefill + greedy decode — or, with ``--fleet``,
the DIMM-fleet timing-table service (``repro.serve.FleetServer``).

``python -m repro.launch.serve --arch qwen2-0.5b --smoke --tokens 16``
``python -m repro.launch.serve --fleet 256 --chunk 128 [--geometry FULL]
[--ckpt-dir D]``

``--metrics-out F`` dumps the obs registry (Prometheus text) and
``--trace-out F`` records the run under the JAX profiler and writes it as
Chrome trace-event JSON: the host spans (``serve.*`` phases) and the
device's programs and ops on one clock — the two observability artifacts
CI uploads per leg.
"""
from __future__ import annotations

import argparse
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.compile_cache import enable_compile_cache
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.data.pipeline import make_batch
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_host_mesh
from repro.models import model as model_mod


def generate(cfg, params, prompt_batch, *, max_new: int = 16):
    """Returns (generated tokens (B, max_new), stats).  Wall times come from
    ``obs`` spans — one code path for the driver's printed stats, the bench
    numbers, and the trace-event timeline.  ``Span.bind`` blocks on the
    bound device value at span close, so a span measures compute, not
    dispatch (jitted calls return asynchronously), on the monotonic clock.
    """
    B, S = prompt_batch["tokens"].shape
    prefill = steps_mod.make_prefill_step(cfg, max_seq=S + max_new)
    decode = steps_mod.make_decode_step(cfg)
    jpre = jax.jit(prefill)
    jdec = jax.jit(decode)
    with obs.span("serve.prefill", batch=B, prompt_len=S) as sp:
        logits, cache = jpre(params, prompt_batch)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        sp.bind(tok)
    t_prefill = sp.duration_s
    with obs.span("serve.decode", batch=B, tokens=max_new) as sp:
        out = [tok]
        for _ in range(max_new - 1):
            tok, cache = jdec(params, cache, {"tokens": tok[:, None]})
            out.append(tok)
        toks = jnp.stack(out, axis=1)
        sp.bind(toks)
    t_decode = sp.duration_s
    return toks, {"prefill_s": t_prefill, "decode_s": t_decode,
                  "tok_per_s": B * (max_new - 1) / max(t_decode, 1e-9)}


def serve_fleet(n_dimms: int, chunk_size: int,
                ckpt_dir: str | None = None, geometry: str = "TINY") -> dict:
    """Stand up the DIMM-fleet timing-table service over a synthetic fleet
    of the named ``core.geometry`` preset: ingest every DIMM, report the
    serving-path split, optionally checkpoint the state, and return the
    ingest stats + staleness report."""
    from repro.core import geometry as geo
    from repro.core.population import synthetic_fleet
    from repro.serve import FleetConfig, FleetServer

    fleet = synthetic_fleet(n_dimms, getattr(geo, geometry), seed=0)
    server = FleetServer(fleet, FleetConfig(chunk_size=chunk_size),
                         checkpoint_dir=ckpt_dir)
    with obs.span("serve.fleet_ingest", n_dimms=n_dimms) as sp:
        stats = server.ingest(now=0.0)
    stats["ingest_s"] = round(sp.duration_s, 2)
    stats.update(server.staleness())
    stats["metrics"] = server.metrics()
    if ckpt_dir is not None:
        server.save(step=0)
    print(f"fleet: {stats['ingested']} DIMMs in {stats['ingest_s']}s -> "
          f"hits={stats['hits']} misses={stats['misses']} "
          f"conventional={stats['conventional']} "
          f"generations={stats['n_generations']}, staleness bound "
          f"{stats['bound_years']:.2f}y"
          + (f", checkpoint -> {ckpt_dir}" if ckpt_dir else ""))
    return stats


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--fleet", type=int, default=0,
                    help="serve a DIMM fleet of this size instead of an LLM")
    ap.add_argument("--chunk", type=int, default=128,
                    help="fleet ingest chunk size (with --fleet)")
    ap.add_argument("--geometry", default="TINY",
                    choices=("TINY", "SMALL", "FULL"),
                    help="DIMM geometry preset of the fleet (with --fleet)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (with --fleet)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the obs registry as Prometheus text here")
    ap.add_argument("--trace-out", default=None,
                    help="record a profiler trace (spans and device "
                         "events); write Chrome trace-event JSON here")
    args = ap.parse_args(argv)

    enable_compile_cache()
    recording = (obs.record(args.trace_out) if args.trace_out
                 else contextlib.nullcontext())
    try:
        with recording:
            if args.fleet:
                stats = serve_fleet(args.fleet, args.chunk, args.ckpt_dir,
                                    args.geometry)
            else:
                cfg = get_smoke_config(args.arch) if args.smoke \
                    else get_config(args.arch)
                params = model_mod.init_params(jax.random.PRNGKey(0), cfg)
                batch = make_batch(cfg, args.batch, args.prompt_len,
                                   seed=0, step=0)
                batch["tokens"] = batch["tokens"][:, :-1]

                with make_host_mesh():
                    toks, stats = generate(cfg, params, batch,
                                           max_new=args.tokens)
                print(f"{args.arch}: generated {toks.shape} "
                      f"prefill={stats['prefill_s']:.2f}s "
                      f"decode={stats['decode_s']:.2f}s "
                      f"({stats['tok_per_s']:.1f} tok/s)")
                assert np.isfinite(np.asarray(toks)).all()
    finally:
        if args.trace_out:
            print(f"trace  -> {args.trace_out}")
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                f.write(obs.REGISTRY.prometheus_text())
            print(f"metrics -> {args.metrics_out}")
    return stats


if __name__ == "__main__":
    main()
