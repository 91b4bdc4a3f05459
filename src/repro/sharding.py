"""Sharding rules: param/activation/cache PartitionSpecs from leaf names.

Baseline layout (see ARCHITECTURE.md):
  - tensor-parallel dims (heads*dh, d_ff, vocab, experts, d_inner) -> "model"
  - an FSDP dim (the other matrix dim) -> "data" when divisible
  - batch -> ("pod", "data") when the pod axis exists, else ("data",)
  - anything non-divisible falls back to replication (recorded, not fatal)

Rules are name-keyed: every param leaf name in models/ maps to a tuple of
mesh-axis requests for its trailing dims; a leading stacked layer dim is
detected by ndim and left unsharded.
"""
from __future__ import annotations

from math import prod

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ------------------------------------------------------- mesh construction

def mesh_axis_types_kw(n_axes: int) -> dict:
    """`make_mesh(axis_types=...)` kwarg: every axis Auto."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def abstract_mesh(shape: tuple, names: tuple):
    return jax.sharding.AbstractMesh(shape, names)


def shard_map(f, mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

# ------------------------------------------------ population (DIMM-axis) mesh

def dimm_mesh(n_devices: int | None = None) -> Mesh:
    """1-D device mesh over the population axis (``"dimms"``) consumed by the
    sharded substrate entry points (``core/substrate.py``'s ``mesh=``).  N
    defaults to every visible device; a single-device mesh is valid and runs
    the same shard_map program — what single-CPU CI exercises — while
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (or a real TPU
    slice) provides true multi-device meshes."""
    import numpy as np
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if not 0 < n <= len(devs):
        raise ValueError(f"dimm_mesh({n_devices}): only {len(devs)} "
                         "device(s) visible")
    return Mesh(np.asarray(devs[:n]), ("dimms",))


def chunk_spans(n_dimms: int, chunk_size: int,
                mesh: Mesh | None = None) -> list[tuple[int, int]]:
    """[lo, hi) population spans for a chunked (streaming) scan.

    The chunk-over-mesh composition rule: when a chunk is itself sharded over
    a DIMM-axis ``mesh``, the chunk size is rounded UP to a multiple of the
    mesh's device count, so every full chunk splits evenly over the devices
    and only the final ragged chunk ever needs the clone-padding of
    ``substrate._run_sharded``.  With no mesh the spans are plain fixed-size
    chunks.  Spans tile [0, n_dimms) exactly, in serial order — the order the
    streaming reductions and the incremental generation clusterer rely on.
    """
    if n_dimms < 0 or chunk_size <= 0:
        raise ValueError(f"need n_dimms >= 0 < chunk_size; got "
                         f"({n_dimms}, {chunk_size})")
    if mesh is not None:
        n_dev = int(mesh.devices.size)
        chunk_size += (-chunk_size) % n_dev
    return [(lo, min(lo + chunk_size, n_dimms))
            for lo in range(0, n_dimms, chunk_size)]


# name -> axis request per trailing dim. "m"=model, "f"=fsdp(data), None=replicate
_RULES: dict[str, tuple] = {
    # embeddings / head
    "tok": ("m", "f"),
    "wlm": ("f", "m"),
    # attention
    "wq": ("f", "m"), "wk": ("f", "m"), "wv": ("f", "m"), "wo": ("m", "f"),
    "bq": ("m",), "bk": ("m",), "bv": ("m",),
    # mlp
    "wi": ("f", "m"), "wg": ("f", "m"), "bi": ("m",), "bo": (None,),
    # moe
    "wr": (None, None),
    "wei": ("m", "f", None), "weg": ("m", "f", None), "weo": ("m", None, "f"),
    # mamba
    "win": ("f", "m"), "wconv": (None, "m"), "bconv": ("m",),
    "wxdt": ("m", None), "wxb": ("m", None), "wxc": ("m", None),
    "wdt": (None, "m"), "bdt": ("m",), "alog": ("m", None),
    "dskip": ("m",), "wout": ("m", "f"),
    # rwkv
    "mu": (None, None), "w0": (None,), "wa": ("f", None), "wb": (None, "f"),
    "u": (None,), "gn_scale": (None,), "mu_ck": (None,),
    "wck": ("f", "m"), "wcv": ("m", "f"),
    # norms / scalars
    "scale": (None,), "bias": (None,), "count": (),
}


def _leaf_name(path) -> str:
    last = path[-1]
    return last.key if hasattr(last, "key") else str(last)


def _resolve(shape, req, mesh: Mesh, fsdp_axes: tuple[str, ...]):
    """Map axis requests onto the mesh with divisibility fallback."""
    entries = []
    used: set[str] = set()
    for dim, r in zip(shape, req):
        if r is None:
            entries.append(None)
            continue
        names = ("model",) if r == "m" else fsdp_axes
        names = tuple(n for n in names if n in mesh.axis_names and n not in used)
        size = prod(mesh.shape[n] for n in names) if names else 0
        if names and size and dim % size == 0:
            entries.append(names if len(names) > 1 else names[0])
            used.update(names)
        else:
            entries.append(None)
    return P(*entries)


def param_spec(path, leaf, mesh: Mesh, fsdp_axes=("data",)) -> P:
    name = _leaf_name(path)
    req = _RULES.get(name)
    shape = leaf.shape
    if req is None:
        return P()
    # allow up to two leading stacked dims (jamba blocks stack sub-stacks)
    extra = len(shape) - len(req)
    if extra < 0:
        return P()
    full = (None,) * extra + tuple(req)
    return _resolve(shape, full, mesh, fsdp_axes)


def param_shardings(tree, mesh: Mesh, fsdp_axes=("data",)):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_spec(path, leaf, mesh, fsdp_axes)), tree)


def opt_state_shardings(opt_state_shapes, params_shapes, mesh: Mesh, fsdp_axes=("data",)):
    """Optimizer-state leaves inherit their param's spec where shapes match;
    adafactor's factored leaves drop the reduced axis."""

    def spec_like(path, leaf):
        # path looks like ("m"|"v"|"f", <param path...>, maybe "vr"/"vc"/"m"/"v")
        keys = [k.key if hasattr(k, "key") else str(k) for k in path]
        # find the param leaf name in the path (the last key that is in _RULES)
        pname = None
        for k in keys[::-1]:
            if k in _RULES:
                pname = k
                break
        if pname is None:
            return P()
        req = _RULES[pname]
        tail = keys[-1]
        if tail == "vr":  # param shape[:-1]
            req = req[:-1]
        elif tail == "vc":  # param shape[:-2] + (C,)
            req = req[:-2] + req[-1:]
        extra = len(leaf.shape) - len(req)
        if extra < 0:
            return P()
        return _resolve(leaf.shape, (None,) * extra + tuple(req), mesh, fsdp_axes)

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_like(path, leaf)), opt_state_shapes)


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _bax(mesh: Mesh, dim: int):
    """Batch axis assignment with divisibility fallback (long_500k has B=1)."""
    b = batch_axes(mesh)
    size = prod(mesh.shape[a] for a in b)
    if b and size and dim % size == 0:
        return b if len(b) > 1 else b[0]
    if "data" in b and dim % mesh.shape["data"] == 0:
        return "data"
    return None


def data_spec(leaf, mesh: Mesh) -> P:
    """Batch-leading arrays: shard dim0 over ("pod","data")."""
    return P(_bax(mesh, leaf.shape[0]), *([None] * (leaf.ndim - 1)))


def batch_shardings(tree, mesh: Mesh):
    return jax.tree.map(lambda leaf: NamedSharding(mesh, data_spec(leaf, mesh)), tree)


def cache_spec(path, leaf, mesh: Mesh) -> P:
    """KV caches (L, B, S, KVH, dh): batch over data axes; kv-heads over
    "model" when divisible, else the *sequence* dim goes to "model" (GQA archs
    with kv_heads < model axis — kimi/internlm/jamba/qwen/paligemma). SSM/RWKV
    states shard batch + the d_inner/head dim."""
    name = _leaf_name(path)
    if name == "pos":
        return P()
    M = mesh.shape["model"]
    if name in ("k", "v", "xk", "xv", "k_scale", "v_scale"):
        bax = _bax(mesh, leaf.shape[1])
        kvh, seq = leaf.shape[3], leaf.shape[2]
        if kvh % M == 0:
            return P(None, bax, None, "model", None)
        if seq % M == 0:
            return P(None, bax, "model", None, None)
        return P(None, bax, None, None, None)
    if name in ("conv", "ssm"):  # (nb, P-1, B, *state)
        spec = [None] * len(leaf.shape)
        spec[2] = _bax(mesh, leaf.shape[2])
        di_dim = 3 if name == "ssm" else 4
        if leaf.shape[di_dim] % M == 0:
            spec[di_dim] = "model"
        return P(*spec)
    if name in ("shift_t", "shift_c"):  # (L, B, 1, D)
        return P(None, _bax(mesh, leaf.shape[1]), None, None)
    if name == "wkv":  # (L, B, H, dh, dh)
        m = "model" if leaf.shape[2] % M == 0 else None
        return P(None, _bax(mesh, leaf.shape[1]), m, None, None)
    return P()


def cache_shardings(tree, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, cache_spec(path, leaf, mesh)), tree)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ----------------------------------------------------------- activation hints

def _ambient_mesh():
    try:
        from jax._src.mesh import thread_resources
        m = thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:  # pragma: no cover - mesh API drift
        return None


def hint(x, *pattern):
    """Best-effort with_sharding_constraint.

    pattern entries per dim: "b" (batch axes), "m" (model), None. Entries are
    dropped when the dim is not divisible or no mesh is active, so model code
    can call this unconditionally (CPU tests run without a mesh).
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    entries = []
    used: set[str] = set()
    for dim, e in zip(x.shape, pattern):
        if e == "b":
            ax = _bax(mesh, dim)
            names = ax if isinstance(ax, tuple) else ((ax,) if ax else ())
            if names and not (set(names) & used):
                entries.append(ax)
                used.update(names)
            else:
                entries.append(None)
        elif e == "m" and "model" in mesh.axis_names and dim % mesh.shape["model"] == 0 \
                and "model" not in used:
            entries.append("model")
            used.add("model")
        else:
            entries.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*entries)))


def hint_heads_or_seq(x):
    """(B, S, H, dh): shard heads on "model" when divisible, else the seq dim
    (sequence-parallel fallback for archs like qwen2-0.5b H=14, paligemma H=8)."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    M = mesh.shape.get("model", 1)
    if x.shape[2] % M == 0:
        return hint(x, "b", None, "m", None)
    return hint(x, "b", "m", None, None)
