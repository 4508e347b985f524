"""The served weights, made by the benchmark from ``--seed`` in one jitted
call on the device, in the type they are served in, straight into their
placement.  The program gives only the layout (names and shapes, from
``jax.eval_shape`` of its ``init_params``); the values are the
benchmark's, so the reference can read them without taking anything the
program made.

Values keep every position's own token visible through the depth (a
random network whose residual branches swamp a small embedding makes
every position alike and every logit row the same): embedding N(0, 1);
q/k/v and MLP input matrices N(0, 1/fan_in); the output matrices of each
residual branch (attention ``o``, MLP ``out``) N(0, 1/(fan_in * 2 *
layers)); LM head N(0, 1/d); biases N(0, 0.02^2); norm gains
``1 + N(0, 0.1^2)``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int, salt: int = 0):
    """A PRNG key from any non-negative whole number (seeds exceed 32 bits)."""
    state = np.random.SeedSequence([int(seed), salt]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32))


def _value(key, path: str, sds, layers: int):
    shape, dtype = sds.shape, sds.dtype
    if path.endswith("scale"):
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if path.endswith("/b"):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if path.startswith("embed/"):
        std = 1.0
    elif path.startswith("lm_head/"):
        std = 1.0 / np.sqrt(shape[-1])
    elif path.endswith(("/o/w", "/out/w")):
        std = 1.0 / np.sqrt(shape[-2] * 2 * layers)
    else:
        std = 1.0 / np.sqrt(shape[-2])
    return (std * jax.random.normal(key, shape, dtype)).astype(dtype)


def make(layout, seed: int, sharding=None):
    """Weights with the tree and dtypes of ``layout`` (a tree of
    ShapeDtypeStruct), one jitted call, written into ``sharding``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    layers = max(int(sds.shape[0]) for p, (_, sds) in zip(paths, flat)
                 if p.startswith("layers/"))

    def gen(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _value(jax.random.fold_in(key, i), path, sds, layers)
            for i, (path, (_, sds)) in enumerate(zip(paths, flat))])

    out = None if sharding is None else jax.tree.map(lambda _: sharding, layout)
    return jax.jit(gen, out_shardings=out)(key_from_seed(seed, 1))
