"""In-graph cross-host telemetry aggregation for the sharded SWAPPER runtime.

The adaptive runtime's telemetry records are built from *sums* (per-bit
occupancy counts, limb-exact error sums, element counts), one *max* (the
worst-case error) and two operand *samples* — so the fleet-global record is
an exact ``psum`` / ``pmax`` / ``all_gather`` over the mesh batch axes,
applied **inside the sharded step** before the records ever leave the trace
(the field classes are owned by ``runtime.telemetry``).  One controller then
re-tunes from the global operand distribution: no host-side gather, no
per-shard policy skew, and the collective costs a few KB per observed step.

``shard_decode_specs`` derives the shard_map partition specs for the serving
step (batch-sharded token/cache leaves, replicated params/policy) from the
same logical-axis rules as ``launch/sharding.axis_rules`` — the mesh batch
axes are exactly the axes the batch dimension maps to ("pod" + "data").
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.runtime.telemetry import (
    MAX_FIELDS,
    SAMPLE_FIELDS,
    SUM_FIELDS,
    operand_summary,
    tile_key,
    tile_summary,
)

__all__ = [
    "batch_axis_names",
    "aggregate_records",
    "shard_decode_specs",
    "token_step_specs",
    "make_sharded_summarizer",
]

def batch_axis_names(mesh: Mesh) -> Tuple[str, ...]:
    """The mesh axes the batch dimension shards over — mirrors the 'batch'
    rule of ``launch.sharding.axis_rules`` ('pod' + 'data')."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _reduce_field(name: str, leaf, axes: Tuple[str, ...]):
    if name in MAX_FIELDS:
        return jax.lax.pmax(leaf, axes)
    if name in SAMPLE_FIELDS:
        # concatenate shard samples along axis -2: the call axis for the
        # scalar records ((ncalls, S) / slot-buffered (slots, ncalls, S)),
        # and the sample axis for the tile records — their samples are laid
        # out (..., S, gm) sample-major precisely so this shared rule
        # extends each tile's sample column instead of inventing new tiles
        return jax.lax.all_gather(leaf, axes, axis=leaf.ndim - 2, tiled=True)
    assert name in SUM_FIELDS, f"unclassified telemetry field {name!r}"
    return jax.lax.psum(leaf, axes)


def aggregate_records(records: Dict[str, Dict[str, jax.Array]],
                      axes: Tuple[str, ...]):
    """Fleet-reduce a scope-collected record tree inside a shard_map'd step.

    Sum fields are ``psum``'d (bit-exact: occupancy counts are small-integer
    float32, limb sums are uint32 within the 32-shard overflow bound),
    ``err_max`` is ``pmax``'d, and the re-tune operand samples are
    all-gathered so the controller's ring buffers see every shard's traffic.
    The result is identical on every shard and bit-equal to the host-side
    ``runtime.telemetry.combine_records`` of the per-shard records.
    """
    if not axes:
        return records
    return {
        target: {k: _reduce_field(k, v, axes) for k, v in rec.items()}
        for target, rec in records.items()
    }


# ---------------------------------------------------------------------------
# partition specs for the sharded decode step
# ---------------------------------------------------------------------------

def _tree_path_strs(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            for path, _ in flat], [leaf for _, leaf in flat]


def cache_pspecs(cache, batch: int, axes: Tuple[str, ...]):
    """PartitionSpec tree sharding each decode-cache leaf's *batch* dim over
    ``axes`` (scan-stacked 'stack/' leaves carry a leading layer dim; the
    encoder-decoder cross-cache layout is not supported in the fleet path)."""
    paths, leaves = _tree_path_strs(cache)
    treedef = jax.tree_util.tree_structure(cache)
    specs = []
    for path, leaf in zip(paths, leaves):
        bdim = 1 if path.startswith("stack/") else 0
        assert leaf.shape[bdim] == batch, (
            f"fleet cache spec: leaf {path} shape {leaf.shape} has no batch "
            f"dim {batch} at axis {bdim}")
        specs.append(P(*([None] * bdim + [axes])))
    return jax.tree_util.tree_unflatten(treedef, specs)


def _batch_axes_checked(batch: int, mesh: Mesh) -> Tuple[str, ...]:
    axes = batch_axis_names(mesh)
    nshard = 1
    for a in axes:
        nshard *= mesh.shape[a]
    assert nshard and batch % nshard == 0, (
        f"fleet serving batch {batch} must divide the mesh batch axes "
        f"{axes} (|{axes}| = {nshard})")
    assert nshard <= 32, (
        f"{nshard} batch shards would overflow the uint32 error-limb psum "
        f"(see runtime.telemetry field classes: bound is 32 shards at "
        f"TELEMETRY_SAMPLE=2048)")
    return axes


def shard_decode_specs(cache, batch: int, mesh: Mesh, seeded: bool = False):
    """(in_specs, out_specs, axes) for the shard_map'd fused adaptive decode
    ``(params, cache, tok0, key0, pos0, budget, bmax, dyn[, seeds]) ->
    (toks, telem)``:

    * params / RNG key / the global-budget-max scalar (the shard-invariant
      telemetry gate) / policy triples are replicated,
    * the token vector, the per-slot position/budget vectors — and, with
      ``seeded``, the per-request seed vector — and every cache leaf shard
      their batch dim,
    * output tokens stay batch-sharded; the telemetry tree is replicated
      (it was psum/pmax/all-gathered inside the step).
    """
    axes = _batch_axes_checked(batch, mesh)
    in_specs = (P(), cache_pspecs(cache, batch, axes), P(axes), P(),
                P(axes), P(axes), P(), P())
    if seeded:
        in_specs = in_specs + (P(axes),)
    out_specs = (P(None, axes), P())
    return in_specs, out_specs, axes


def token_step_specs(cache, batch: int, mesh: Mesh, seeded: bool = False):
    """(in_specs, out_specs, axes) for the shard_map'd token-granular step
    ``(params, cache, tok, sub, pos, active, dyn, gate[, seeds, nt]) ->
    (tok, cache, telem)``: per-slot vectors (including the per-request
    seed/token-counter vectors when ``seeded``) and cache leaves shard
    their batch dim, everything else is replicated (the telemetry tree was
    aggregated in-graph)."""
    axes = _batch_axes_checked(batch, mesh)
    cspecs = cache_pspecs(cache, batch, axes)
    in_specs = (P(), cspecs, P(axes), P(), P(axes), P(axes), P(), P())
    if seeded:
        in_specs = in_specs + (P(axes), P(axes))
    out_specs = (P(axes), cspecs, P())
    return in_specs, out_specs, axes


# ---------------------------------------------------------------------------
# model-free sharded summarizer (benchmarks / synthetic fleet streams)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def make_sharded_summarizer(mult_name: str, mesh: Mesh, target: str = "stream",
                            tile_rows: int = 0):
    """jit(shard_map(...)) producing the fleet-aggregated telemetry record of
    a raw int operand pair stream sharded over the mesh batch axes.  Feed the
    result straight to ``AdaptiveController.observe`` — the controller then
    re-tunes from the *global* operand distribution while each shard only
    ever summarized its local slice.

    ``tile_rows > 0`` additionally emits the per-row-tile record (sharding a
    2-D stream's *rows*, i.e. each shard summarizes its local row slice at
    ``tile_rows`` tiles): the returned dict then maps both ``target`` and
    ``tile_key(target)`` to fleet-aggregated records — tile histograms psum
    position-wise (shard-local row tile t pools into fleet tile t), tile
    samples all-gather along the sample axis, so the controller's per-tile
    re-tune sees every shard's traffic for each tile position."""
    from repro.core import multipliers as M

    mult = M.get(mult_name)
    axes = batch_axis_names(mesh)
    nshard = 1
    for a in axes:
        nshard *= mesh.shape[a]
    assert nshard <= 32, (
        f"{nshard} shards would overflow the uint32 error-limb psum")

    def local(a, b, dyn):
        rec = operand_summary(a, b, mult, dyn)
        if tile_rows == 0:                   # original single-record surface
            rec = {k: v[None] for k, v in rec.items()}   # leading call axis
            return aggregate_records({target: rec}, axes)[target]
        trec = tile_summary(a, b, mult, tile_rows, dyn=dyn)
        recs = {target: {k: v[None] for k, v in rec.items()},
                tile_key(target): {k: v[None] for k, v in trec.items()}}
        return aggregate_records(recs, axes)

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(axes), P(axes), P()), out_specs=P(),
                      check_vma=False)
    return jax.jit(f)
