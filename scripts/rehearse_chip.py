"""Compile the chip smoke's programs for a DESCRIBED TPU v5e, from shapes.

    JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/rehearse_chip.py [NAME ...]

No chip is attached: the TPU compiler that ships with JAX compiles for a
``v5e:2x2`` topology it is only told about, and refuses what the chip
would refuse (tiling, fast-memory limits, a program that does not fit
the device's memory). Each case prints ``memory_analysis()`` per device.
Nothing runs, so this says nothing about results or times.

Cases (the shapes of ``chip_smoke.py``):

* ``a_segment`` / ``a_eval``: FACADE GN-LeNet, 32 nodes, one 40-round
  segment program and the per-cluster evaluator (30 nodes, batch 256);
* ``c_segment`` / ``c_eval``: FACADE ResNet8 (64x64, 41 classes), 32
  nodes, a 20-round segment and its evaluator;
* ``mesh1_segment`` / ``mesh1_eval``: FACADE GN-LeNet, 1,024 nodes, one
  40-round segment on ONE chip and the 960-node evaluator (batch 8);
* ``mesh4_segment``: the same segment sharded over the four chips'
  ``node`` mesh, with the collectives the compiler put in.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs.facade_paper import lenet, resnet8  # noqa: E402
from repro.core import meshctx, runner  # noqa: E402
from repro.core.bindings import make_binding  # noqa: E402
from repro.core.engine import SegmentEngine  # noqa: E402

DEGREE, LOCAL_STEPS, BATCH, LR = 4, 10, 8, 0.05


def _program(cfg, n, k=2):
    binding = make_binding(cfg)
    prog = runner.algo_program("facade", binding, n, k, degree=DEGREE,
                               local_steps=LOCAL_STEPS, lr=LR)
    return binding, prog


def _engine(prog, n, mesh=None):
    return SegmentEngine(prog.round_fn, warmup_fn=prog.warmup_fn, n=n,
                         local_steps=LOCAL_STEPS, batch_size=BATCH,
                         track_cluster=prog.track_cluster,
                         mixable_of=prog.mixable_of, mesh=mesh)


def _sds(tree, shardings):
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)


def segment(cfg, n, samples, length, sharding_of):
    """Lower + compile one segment program. ``sharding_of(tree)`` gives
    the placement pytree for a node-stacked pytree of shapes."""
    _, prog = _program(cfg, n)
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(prog.init_state, key)
    carry = jax.eval_shape(_engine(prog, n).init_carry, state, key)
    s = cfg.image_size
    tx = jax.ShapeDtypeStruct((n, samples, s, s, cfg.channels), jnp.float32)
    ty = jax.ShapeDtypeStruct((n, samples), jnp.int32)
    mesh = sharding_of.mesh
    fn = _engine(prog, n, mesh=mesh)._build(length, False)
    args = (_sds(carry, sharding_of(carry, n)),
            jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=sharding_of.scalar),
            *(_sds(a, sharding_of(a, n)) for a in (tx, ty)))
    return fn.lower(*args).compile()


def evaluator(cfg, n_cluster, test_per_class, batch, chip):
    binding, _ = _program(cfg, n_cluster, k=1)
    s = cfg.image_size
    m = cfg.n_classes * test_per_class
    test_x = [np.zeros((m, s, s, cfg.channels), np.float32)]
    test_y = [np.zeros((m,), np.int32)]
    ev = runner.make_evaluator(binding, np.zeros(n_cluster, np.int32),
                               test_x, test_y, batch=batch)
    params = jax.eval_shape(
        jax.vmap(binding.init),
        jax.ShapeDtypeStruct((n_cluster, 2), jnp.uint32))
    (models_c, xb), = jax.eval_shape(ev.inputs, params)
    place = SingleDeviceSharding(chip)
    return ev.predict.lower(
        _sds(models_c, jax.tree.map(lambda _: place, models_c)),
        jax.ShapeDtypeStruct(xb.shape, xb.dtype, sharding=place)).compile()


class OneChip:
    def __init__(self, chip):
        self.mesh = None
        self.scalar = SingleDeviceSharding(chip)

    def __call__(self, tree, n):
        return jax.tree.map(lambda _: self.scalar, tree)


class NodeMesh:
    def __init__(self, devices):
        self.mesh = Mesh(np.asarray(devices), (meshctx.NODE_AXIS,))
        self.scalar = NamedSharding(self.mesh, P())

    def __call__(self, tree, n):
        return meshctx.carry_shardings(self.mesh, tree, n)


def main(argv) -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = topo.devices[0]
    one, four = OneChip(chip), NodeMesh(topo.devices)
    cases = {
        "a_segment": lambda: segment(lenet(), 32, 320, 40, one),
        "a_eval": lambda: evaluator(lenet(), 30, 64, 256, chip),
        "c_segment": lambda: segment(resnet8(), 32, 164, 20, one),
        "c_eval": lambda: evaluator(resnet8(), 30, 16, 256, chip),
        "mesh1_segment": lambda: segment(lenet(), 1024, 40, 40, one),
        "mesh1_eval": lambda: evaluator(lenet(), 960, 64, 8, chip),
        "mesh4_segment": lambda: segment(lenet(), 1024, 40, 40, four),
    }
    names = argv or list(cases)
    print(f"target: {chip.device_kind}, {len(topo.devices)} chips described")
    for name in names:
        t0 = time.perf_counter()
        compiled = cases[name]()
        dt = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        coll = {op: text.count(f" {op}(") for op in
                ("all-gather", "all-reduce", "reduce-scatter",
                 "collective-permute", "all-to-all")}
        print(f"{name}: compiled in {dt:.1f} s; per device: "
              f"arguments {ma.argument_size_in_bytes}, "
              f"outputs {ma.output_size_in_bytes}, "
              f"aliased {ma.alias_size_in_bytes}, "
              f"temporaries {ma.temp_size_in_bytes}, "
              f"code {ma.generated_code_size_in_bytes} bytes; "
              f"collectives {({k: v for k, v in coll.items() if v})}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
