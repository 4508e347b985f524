"""Prepared approximate projections (``quant.ax.prepare_params``): a weight
quantized and limb-built once at load gives the same outputs and the same
telemetry records, bit for bit, as the raw weight quantized on every call,
on every backend and under every kind of swap decision; and the prepared
adaptive decode step reads the weight side as int8 only."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AxPolicy
from repro.kernels.schedule import KernelSchedule, ScheduleTable, install_table
from repro.models.layers import dense
from repro.quant.ax import prepare_params, prepared_projections
from repro.runtime import ax_scope

B, S, K, N = 2, 4, 64, 32        # M = B*S = 8 activation rows

# (backend, multiplier, mxu limb schedule); trunc2_2 has an f that is not
# the identity, so its record stores wq beside wfg
CASES = [
    ("mxu", "mul8s_trunc0_4", None),
    ("mxu", "mul8s_trunc2_2", None),
    ("mxu", "mul8s_perf0_1", None),
    ("mxu", "mul8s_trunc0_4", "split"),
    ("mxu", "mul8s_trunc0_4", "noswap_fast"),
    ("emul", "mul8s_trunc0_4", None),
    ("kernel", "mul8s_trunc0_4", None),
]
# static policies (no scope) and traced triples (adaptive scope), one grid
KINDS = {
    "static_noswap": None, "static_a": ("A", 3, 0), "static_b": ("B", 5, 1),
    "dyn_a": (1, 3, 0), "dyn_b": (0, 5, 1), "dyn_noswap": (1, 0, 2),
    # per-row tiles: A-side, NoSwap, a uniform B-side triple, A-side
    "tiles": ((1, 3, 0), (0, 0, 2), (0, 4, 1), (1, 6, 1)),
}


def _policy(backend, mult, kind):
    triple = KINDS[kind]
    if kind.startswith("static") and triple is not None:
        return AxPolicy(mult_name=mult, backend=backend, swap_operand=triple[0],
                        swap_bit=triple[1], swap_value=triple[2])
    return AxPolicy(mult_name=mult, backend=backend,
                    swap_enabled=kind != "static_noswap")


def _schedule_table(mult, mode):
    sched = KernelSchedule(backend="mxu", limbs="split" if mode == "split"
                           else "stacked", noswap_fast=mode == "noswap_fast")
    table = ScheduleTable()
    for op in ("int_static", "int_dyn"):
        table.set(B * S, K, N, "mxu", mult, op, sched)
    return table


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("backend,mult,limbs", CASES)
def test_prepared_dense_bit_identical(backend, mult, limbs, kind):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, S, K)), jnp.bfloat16)
    raw = {"in": {"w": jnp.asarray(rng.normal(size=(K, N)) / 8, jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(N,)), jnp.float32)}}
    pol = _policy(backend, mult, kind)
    cfg = types.SimpleNamespace(ax=pol, compute_dtype="bfloat16")
    prep = prepare_params(raw, cfg)
    rec = prep["in"]
    assert "w" not in rec and rec["b"] is raw["in"]["b"]
    assert ("wfg" in rec) == (backend == "mxu")
    assert ("wq" in rec) == (backend != "mxu" or mult == "mul8s_trunc2_2")
    assert prepared_projections(prep) == 1 and prepared_projections(raw) == 0
    assert "w" in raw["in"]                  # the input tree is left as it was

    triple = KINDS[kind]
    dyn = None
    if kind == "tiles":
        dyn = jnp.asarray(triple, jnp.int32)[:, None, :]
    elif kind.startswith("dyn"):
        dyn = jnp.asarray(triple, jnp.int32)
    tile_rows = dyn.shape[0] if dyn is not None and dyn.ndim == 3 else 0

    def run(p, d):
        if d is None:
            return dense(x, p, pol, "mlp"), {}
        # the kernel backend takes its tile statistic from the kernel
        with ax_scope({"mlp": d}, collect=True, tile_rows=tile_rows,
                      kernel_hist=backend == "kernel") as sc:
            y = dense(x, p, pol, "mlp")
            return y, sc.collected()

    prev = install_table(_schedule_table(mult, limbs) if limbs else None)
    try:
        want = jax.device_get(jax.jit(run)(raw["in"], dyn))
        got = jax.device_get(jax.jit(run)(rec, dyn))
    finally:
        install_table(prev)
    assert np.array_equal(np.asarray(got[0], np.float32),
                          np.asarray(want[0], np.float32))
    flat_w, tree_w = jax.tree_util.tree_flatten(want[1])
    flat_g, tree_g = jax.tree_util.tree_flatten(got[1])
    assert tree_w == tree_g and (dyn is None or flat_w)
    for a, b in zip(flat_w, flat_g):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the prepared adaptive token step: no requantization, one int8 dot per
# projection in each branch of its swap-side cond
# ---------------------------------------------------------------------------

def _step_jaxpr(prepared):
    import repro.configs as CFG
    from repro.models import init_cache, init_params
    from repro.serve.engine import _token_step_fn

    # widths chosen so every approximated weight shape is unique to it
    cfg = dataclasses.replace(CFG.reduced(CFG.ARCHS["qwen2-72b"]), n_layers=2,
                              d_model=48, n_heads=4, n_kv_heads=2, head_dim=8,
                              d_ff=80, vocab=96, ax=AxPolicy(backend="mxu"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    if prepared:
        params = prepare_params(params, cfg)
    nb = 3
    cache = init_cache(cfg, nb, 16)
    step = _token_step_fn(cfg, None, 0.0, True, None, cache, nb)
    i32 = jnp.zeros((nb,), jnp.int32)
    dyn = {t: jnp.asarray((1, 3, 0), jnp.int32) for t in cfg.ax.targets}
    jaxpr = jax.make_jaxpr(step)(params, cache, i32, jax.random.PRNGKey(0),
                                 i32, jnp.ones((nb,), bool), dyn,
                                 jnp.bool_(True))
    weights = {(48, 80), (80, 48), (32, 48)}     # in/gate, out, o
    return jaxpr.jaxpr, weights


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            if hasattr(sub, "jaxpr"):
                yield sub.jaxpr
            elif hasattr(sub, "eqns"):
                yield sub


def _float_ops_on(jaxpr, shapes):
    """Primitives of float ops (any float operand or result) that take an
    operand of one of ``shapes``, looking inside nested jaxprs (cond, pjit)
    rather than at the ops that hold them."""
    hits = []
    for eqn in jaxpr.eqns:
        subs = list(_subjaxprs(eqn))
        for sub in subs:
            hits += _float_ops_on(sub, shapes)
        if subs:
            continue
        avals = [v.aval for v in list(eqn.invars) + list(eqn.outvars)
                 if hasattr(v, "aval")]
        is_float = any(jnp.issubdtype(a.dtype, jnp.floating) for a in avals)
        if is_float and any(tuple(getattr(v.aval, "shape", ())) in shapes
                            for v in eqn.invars if hasattr(v, "aval")):
            hits.append(eqn.primitive.name)
    return hits


def _int8_dots(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "dot_general"
                and eqn.invars[0].aval.dtype == jnp.int8):
            n += 1
        for sub in _subjaxprs(eqn):
            n += _int8_dots(sub)
    return n


def _dot_conds(jaxpr):
    """The branch lists of every cond holding an int8 dot."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            branches = [b.jaxpr for b in eqn.params["branches"]]
            if any(_int8_dots(b) for b in branches):
                out.append(branches)
                continue
        for sub in _subjaxprs(eqn):
            out += _dot_conds(sub)
    return out


def _cond_operands(jaxpr):
    """Shapes of every operand of every cond, recursing into nested jaxprs."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            out += [tuple(v.aval.shape) for v in eqn.invars]
        for sub in _subjaxprs(eqn):
            out += _cond_operands(sub)
    return out


def test_prepared_step_reads_weights_as_int8_once():
    jaxpr, weights = _step_jaxpr(prepared=True)
    assert _float_ops_on(jaxpr, weights) == []
    # a layer's weight slice never enters a cond (XLA would copy it there):
    # the conds take the stacked arrays and slice inside their branches
    sliced = weights | {(2,) + s for s in weights}
    assert not set(_cond_operands(jaxpr)) & sliced
    conds = _dot_conds(jaxpr)
    assert len(conds) == 8                   # 2 layers x (in, gate, out, o)
    for branches in conds:
        assert [_int8_dots(b) for b in branches] == [1, 1]
    assert _int8_dots(jaxpr) == 16           # none outside the conds
    # the same gate flags the raw step, which requantizes every weight
    raw, _ = _step_jaxpr(prepared=False)
    assert "convert_element_type" in _float_ops_on(raw, weights)


def test_prepared_leaves_shard_like_their_weight():
    """The sharding rules place each prepared leaf as the weight it
    replaces: ``wq`` as ``w``, ``wfg`` with its limb axis unsharded, ``sw``
    along N; a prepared tree gets a sharding for every leaf."""
    import repro.configs as CFG
    from repro.configs.base import ParallelConfig
    from repro.launch.mesh import make_mesh, param_shardings
    from repro.models import init_params
    from repro.models.layers import axes_for_path

    w = axes_for_path("layers/mlp/out/w", 3)
    assert w == ("layers", "ff", "embed")
    assert axes_for_path("layers/mlp/out/wq", 3) == w
    assert axes_for_path("layers/mlp/out/wfg", 4) == ("layers", None, "ff", "embed")
    assert axes_for_path("layers/mlp/out/sw", 3) == ("layers", None, "embed")
    assert axes_for_path("attn/o/wfg", 3) == (None, "heads", "embed")

    cfg = dataclasses.replace(CFG.reduced(CFG.ARCHS["qwen2-72b"]), n_layers=2,
                              ax=AxPolicy(mult_name="mul8s_trunc2_2"))
    prep = prepare_params(init_params(jax.random.PRNGKey(0), cfg), cfg)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    sh = param_shardings(mesh, ParallelConfig(), prep)
    assert len(jax.tree.leaves(sh)) == len(jax.tree.leaves(prep))
    assert prepared_projections(prep) == 8
