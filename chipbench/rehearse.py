"""Compile a cell's segment program and evaluator for a DESCRIBED TPU v5e.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py <cell> [<cell> ...]

No chip is attached: the TPU compiler that ships with JAX compiles for a
``v5e:2x2`` topology it is only told about, and refuses what the chip
would (a program that does not fit the device's memory, among others).
For each cell it prints ``memory_analysis()`` per device of the
``(eval_every, warmup=False)`` segment program and of the evaluator of
the largest cluster. Nothing runs: this says nothing of results or times.
"""
from __future__ import annotations

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _sds(tree, shardings):
    import jax

    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)


def programs(cell: dict, topo):
    """(name, compiled) of the cell's segment program and evaluator."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from chipbench import spec
    from repro.core import meshctx, runner
    from repro.core.bindings import make_binding
    from repro.core.engine import SegmentEngine

    cfg = spec.cnn_config(cell["model"])
    n, k = sum(cell["clusters"]), len(cell["clusters"])
    chips = cell["chips"]
    chip = topo.devices[0]
    if chips == 1:
        mesh = None
        one = SingleDeviceSharding(chip)
        place = lambda tree: jax.tree.map(lambda _: one, tree)  # noqa: E731
        scalar = one
    else:
        mesh = Mesh(np.asarray(topo.devices[:chips]), (meshctx.NODE_AXIS,))
        place = lambda tree: meshctx.carry_shardings(mesh, tree, n)  # noqa
        scalar = NamedSharding(mesh, P())
    binding = make_binding(cfg)
    prog = runner.algo_program(cell["algo"], binding, n, k,
                               degree=cell["degree"],
                               local_steps=cell["local_steps"], lr=cell["lr"])
    eng = SegmentEngine(prog.round_fn, warmup_fn=prog.warmup_fn, n=n,
                        local_steps=cell["local_steps"],
                        batch_size=cell["batch_size"],
                        track_cluster=prog.track_cluster,
                        mixable_of=prog.mixable_of, mesh=mesh)
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(prog.init_state, key)
    carry = jax.eval_shape(eng.init_carry, state, key)
    s, c = cfg.image_size, cfg.channels
    per_node = cfg.n_classes * cell["train_per_class"]
    tx = jax.ShapeDtypeStruct((n, per_node, s, s, c), jnp.float32)
    ty = jax.ShapeDtypeStruct((n, per_node), jnp.int32)
    fn = eng._build(cell["eval_every"], False)
    seg = fn.lower(_sds(carry, place(carry)),
                   jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar),
                   *(_sds(a, place(a)) for a in (tx, ty))).compile()
    yield "segment", seg

    m = max(cell["clusters"])
    tests = cfg.n_classes * cell["test_per_class"]
    ev = runner.make_evaluator(
        binding, np.zeros(m, np.int32),
        [np.zeros((tests, s, s, c), np.float32)],
        [np.zeros((tests,), np.int32)], batch=cell["eval_batch"])
    params = jax.eval_shape(jax.vmap(binding.init),
                            jax.ShapeDtypeStruct((m, 2), jnp.uint32))
    (models_c, xb), = jax.eval_shape(ev.inputs, params)
    one = SingleDeviceSharding(chip)
    yield "evaluator", ev.predict.lower(
        _sds(models_c, jax.tree.map(lambda _: one, models_c)),
        jax.ShapeDtypeStruct(xb.shape, xb.dtype, sharding=one)).compile()


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from jax.experimental import topologies

    from chipbench import spec

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in argv:
        cell = spec.workload(name)
        for prog, compiled in programs(cell, topo):
            ma = compiled.memory_analysis()
            print(f"{name} {prog}: per device arguments "
                  f"{ma.argument_size_in_bytes}, outputs "
                  f"{ma.output_size_in_bytes}, aliased "
                  f"{ma.alias_size_in_bytes}, temporaries "
                  f"{ma.temp_size_in_bytes}, code "
                  f"{ma.generated_code_size_in_bytes} bytes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
