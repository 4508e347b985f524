"""Production mesh construction + state-sharding builders.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): (16, 16) "data" x "model" single-pod (256 chips), or
(2, 16, 16) "pod" x "data" x "model" for the 512-chip multi-pod dry-run.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models.layers import axes_for_path

from .sharding import axis_rules, param_spec

__all__ = [
    "make_mesh",
    "make_production_mesh",
    "make_fleet_mesh",
    "param_shardings",
    "state_shardings",
    "batch_shardings",
    "cache_shardings",
    "tree_paths",
]


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the model code places
    activations with ``with_sharding_constraint`` (``launch.sharding.shard``),
    which accepts only Auto axes (``jax.make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_fleet_mesh(n_replicas: Optional[int] = None):
    """1-D ("data",) serving mesh: each replica holds full weights and
    serves its slice of the slot batch; the fleet telemetry psums over this
    axis (``fleet.collect``).  Defaults to every visible device — on a CPU
    host, force a multi-device fleet with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    initializes (see examples/fleet_serve.py and tests/test_fleet.py)."""
    n = n_replicas or len(jax.devices())
    assert len(jax.devices()) >= n, (n, jax.devices())
    return make_mesh((n,), ("data",), devices=jax.devices()[:n])


def tree_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = [
        "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        for path, _ in flat
    ]
    return paths, [leaf for _, leaf in flat], treedef


def param_shardings(mesh: Mesh, par: ParallelConfig, params_shape):
    """NamedSharding tree for a params pytree (of ShapeDtypeStructs)."""
    paths, leaves, treedef = tree_paths(params_shape)
    out = []
    for path, leaf in zip(paths, leaves):
        # whisper stacked decoder/encoder params count as scan-stacked
        norm = path
        if path.startswith(("layers_enc/", "layers_dec/")):
            norm = "layers/" + path.split("/", 1)[1]
        elif path.startswith("layers/"):
            norm = "layers/" + path.split("/", 2)[2]  # drop the p{j} segment
        axes = axes_for_path(norm, len(leaf.shape))
        out.append(NamedSharding(mesh, param_spec(axes, mesh, par, leaf.shape)))
    return jax.tree_util.tree_unflatten(treedef, out)


def state_shardings(mesh: Mesh, par: ParallelConfig, state_shape):
    """Shardings for the full train state {params, opt:{step,m,v[,ef]}} —
    optimizer moments follow their parameter's sharding (ZeRO-style)."""
    ps = param_shardings(mesh, par, state_shape["params"])
    out = {"params": ps, "opt": {"step": NamedSharding(mesh, P())}}
    for k in state_shape["opt"]:
        if k == "step":
            continue
        out["opt"][k] = ps
    return out


def _batch_axes(mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def _div(dim, mesh, ax):
    names = ax if isinstance(ax, tuple) else ((ax,) if ax else ())
    size = int(np.prod([mesh.shape[n] for n in names])) if names else 1
    return size > 0 and dim % size == 0


def batch_shardings(mesh: Mesh, batch_specs):
    """Input batch: shard the leading (global-batch) dim over pod+data; fall
    back to replication when not divisible (e.g. global_batch=1)."""
    b = _batch_axes(mesh)

    def spec(leaf):
        parts = [None] * len(leaf.shape)
        if len(leaf.shape) >= 1 and _div(leaf.shape[0], mesh, b):
            parts[0] = b
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(spec, batch_specs)


def cache_shardings(mesh: Mesh, par: ParallelConfig, cache_shape, cfg: ModelConfig):
    """Decode-cache shardings: KV caches shard batch over pod+data and the
    cache *sequence* dim over 'model' (decode attention then combines
    partial softmax stats with small all-reduces — GQA kv-head counts are
    frequently smaller than the model axis, so head-sharding is not an
    option at (16,16)).  Recurrent/SSM states shard batch only.  long_500k
    (batch=1) falls back to sequence-over-everything."""
    b = _batch_axes(mesh)
    paths, leaves, treedef = tree_paths(cache_shape)
    out = []
    for path, leaf in zip(paths, leaves):
        shp = leaf.shape
        nd = len(shp)
        leafname = path.rsplit("/", 1)[-1]
        stacked = path.startswith(("stack/", "self/", "cross")) or (
            cfg.family == "encdec"
        )
        parts = [None] * nd
        # locate the batch dim: stacked caches have a leading layer dim
        bdim = 0
        if stacked and nd >= 2:
            bdim = 1
            if "cross" in path and nd >= 3:
                bdim = 2
        if leafname in ("k", "v") or "cross" in path:
            sdim = bdim + 1
            if _div(shp[bdim], mesh, b):
                parts[bdim] = b
                if _div(shp[sdim], mesh, "model"):
                    parts[sdim] = "model"
            else:
                # batch=1 long-context: shard the sequence over both axes
                both = tuple(x for x in ((b if isinstance(b, tuple) else (b,)) + ("model",)) if x)
                if _div(shp[sdim], mesh, both):
                    parts[sdim] = both
                elif _div(shp[sdim], mesh, "model"):
                    parts[sdim] = "model"
        else:  # recurrent/ssm states, conv buffers
            if _div(shp[bdim], mesh, b):
                parts[bdim] = b
        out.append(NamedSharding(mesh, P(*parts)))
    return jax.tree_util.tree_unflatten(treedef, out)
