"""Compile each cell's token step and largest-bucket prefill at full width
for a described TPU v5e (no chip needed) and print what the compiler
says each program holds on a device.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py

A compile that passes is not a chip run: it shows the programs fit and
are accepted, nothing about time.
"""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from harness import serving, spec
    from repro.launch.mesh import make_mesh
    from repro.models import init_cache, init_params
    from repro.serve.engine import _prefill_one_fn, _token_step_fn

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        sp = spec.resolve(w["name"])
        mix, chips = sp["traffic"], int(w["chips"])
        cfg = serving.program_config(sp["config"], sp["reference"])
        mesh = make_mesh((chips,), ("data",), devices=topo.devices[:chips])
        rep = NamedSharding(mesh, P())

        def sds(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=rep), tree)

        B = mix["slots_per_chip"] * chips
        mcl = max(mix["prompt_buckets"]) + mix["new_token_bucket"] + 1
        params = sds(jax.eval_shape(lambda k: init_params(k, cfg),
                                    jax.random.PRNGKey(0)))
        cache = sds(jax.eval_shape(lambda: init_cache(cfg, B, mcl)))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
        dyn = {t: i32(3) for t in cfg.ax.targets}
        step = _token_step_fn(cfg, None, 0.0, True, mesh, cache, B)
        compiled = step.lower(
            params, cache, i32(B), key, i32(B),
            jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=rep), dyn,
            jax.ShapeDtypeStruct((), jnp.bool_, sharding=rep)).compile()
        _report(w["name"], f"token step ({B} slots, cache {mcl})", compiled)
        bucket = max(mix["prompt_buckets"])
        pre = _prefill_one_fn(cfg, None, bucket, mcl, 0.0, False)
        compiled = pre.lower(params, i32(1, bucket), i32(1), key).compile()
        _report(w["name"], f"prefill of {bucket}", compiled)
    return 0


def _report(cell_name, what, compiled):
    m = compiled.memory_analysis()
    print(f"{cell_name}: {what}: arguments "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, outputs "
          f"{m.output_size_in_bytes / 1e9:.3f} GB (per device)", flush=True)


if __name__ == "__main__":
    sys.exit(main())
