"""Sharded segment engine (ROADMAP Open Item 1): the node-axis mesh.

The parity contract this file pins (and ROADMAP's "Sharding contract"
section documents):

* ``mesh=None`` is the historical single-device path — untouched by
  construction (it never activates a :mod:`repro.core.meshctx` context,
  so the traced jaxpr is unchanged).
* ``mesh=(1,)`` is BIT-EXACT against ``mesh=None`` for every algorithm,
  including under the netsim-v2 edge preset + fault injection + in-scan
  telemetry: a one-device mesh reorders nothing.
* On a REAL multi-device mesh (forced host devices, subprocess), comm
  BYTES stay exact (PRNG draws and topology are layout-independent)
  while accuracies may drift within a small tolerance: per-node conv
  accumulation order differs inside shard_map row blocks, and FACADE's
  argmin head selection can flip on last-bit ties. Tests must NOT assert
  multi-device bit-exactness of accuracies.
* The mesh SHAPE is an :class:`EngineSpec` key field — sharded and
  unsharded runs never share compiled programs.
"""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.facade_paper import lenet
from repro.core import meshctx
from repro.core.cache import EngineCache, EngineSpec
from repro.core.runner import run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.netsim import NetworkConfig
from repro.obs import Obs, ObsConfig
from repro.resil import FaultConfig

pytestmark = pytest.mark.tier0

REPO = pathlib.Path(__file__).resolve().parent.parent
CFG = lenet(smoke=True).replace(n_classes=4)
ALGOS = ("facade", "el", "dpsgd", "deprl", "dac")
KW = dict(rounds=4, k=2, degree=2, local_steps=2, batch_size=4, lr=0.05,
          eval_every=2, seed=0)


@pytest.fixture(scope="module")
def tiny_ds():
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=8,
                     test_per_class=8, seed=3)
    return make_clustered_data(spec, cluster_sizes=(3, 1),
                               transforms=("rot0", "rot180"))


def _assert_runs_identical(ref, got):
    assert ref.acc_per_cluster == got.acc_per_cluster
    assert ref.fair_acc == got.fair_acc
    assert ref.dp == got.dp and ref.eo == got.eo
    assert ref.final_acc == got.final_acc
    assert ref.comm.rounds == got.comm.rounds
    assert ref.comm.bytes == got.comm.bytes          # exact float equality
    assert ref.comm.seconds == got.comm.seconds
    assert ref.comm.evaled == got.comm.evaled
    assert len(ref.cluster_history) == len(got.cluster_history)
    for (r1, c1), (r2, c2) in zip(ref.cluster_history, got.cluster_history):
        assert r1 == r2
        np.testing.assert_array_equal(c1, c2)


# --------------------------------------------- mesh=(1,) exact parity -----
@pytest.mark.parametrize("algo", ALGOS)
def test_mesh1_bitforbit_under_full_stack(algo, tiny_ds):
    """A one-device mesh must be bit-exact vs ``mesh=None`` for every
    algorithm, stacked with the edge-v2 preset, nan-corrupting fault
    injection AND in-scan telemetry — the full driver feature surface.
    The sharded code path (shard_map contractions, layout constraints,
    sharded carry placement) runs; with one shard it may reorder
    nothing."""
    net = dataclasses.replace(
        NetworkConfig.preset("edge-v2"),
        faults=FaultConfig(crash_rate=0.1, restart_rate=0.5,
                           corrupt_rate=0.2, corrupt_mode="nan"))
    ref = run_experiment(algo, CFG, tiny_ds, net=net,
                         obs=Obs(config=ObsConfig()), **KW)
    got = run_experiment(algo, CFG, tiny_ds, net=net,
                         obs=Obs(config=ObsConfig()), mesh=(1,), **KW)
    _assert_runs_identical(ref, got)


def test_mesh1_plain_parity_and_cache_reuse(tiny_ds):
    """No-net sanity: mesh=(1,) through a shared EngineCache still equals
    mesh=None, and the meshed cell warms its own entry (second seeded run
    is a hit, not a rebuild)."""
    cache = EngineCache()
    ref = run_experiment("facade", CFG, tiny_ds, **KW)
    got = run_experiment("facade", CFG, tiny_ds, mesh=(1,), cache=cache,
                         **KW)
    _assert_runs_identical(ref, got)
    assert cache.misses == 1
    again = run_experiment("facade", CFG, tiny_ds, mesh=(1,), cache=cache,
                           **KW)
    _assert_runs_identical(ref, again)
    assert cache.hits >= 1 and cache.misses == 1


# ------------------------------------------ 8 forced devices (child) ------
def test_eight_device_parity_subprocess(tiny_ds):
    """All 5 algorithms on a REAL 8-device mesh (forced host devices —
    must be set before jax imports, hence the subprocess): comm bytes are
    EXACT vs mesh=None, accuracies within tolerance (shard_map row blocks
    change per-node conv accumulation order; see module docstring)."""
    child = r"""
import dataclasses, json, os, sys
import numpy as np
from repro.core.runner import run_experiment
from repro.configs.facade_paper import lenet
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.netsim import NetworkConfig
from repro.resil import FaultConfig
from repro.obs import Obs, ObsConfig
import jax
spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=8,
                 test_per_class=8, seed=3)
ds = make_clustered_data(spec, cluster_sizes=(6, 2),
                         transforms=("rot0", "rot180"))
cfg = lenet(smoke=True).replace(n_classes=4)
net = dataclasses.replace(
    NetworkConfig.preset("edge-v2"),
    faults=FaultConfig(crash_rate=0.1, restart_rate=0.5,
                       corrupt_rate=0.2, corrupt_mode="nan"))
kw = dict(rounds=4, k=2, degree=2, local_steps=2, batch_size=4, lr=0.05,
          eval_every=2, seed=0, net=net)
out = {"n_devices": len(jax.devices())}
for algo in ("facade", "el", "dpsgd", "deprl", "dac"):
    ref = run_experiment(algo, cfg, ds, obs=Obs(config=ObsConfig()), **kw)
    got = run_experiment(algo, cfg, ds, obs=Obs(config=ObsConfig()),
                         mesh=(8,), **kw)
    ra = np.array([v for _, vs in ref.acc_per_cluster for v in vs])
    ga = np.array([v for _, vs in got.acc_per_cluster for v in vs])
    out[algo] = {"bytes_exact": ref.comm.bytes == got.comm.bytes,
                 "sec_exact": ref.comm.seconds == got.comm.seconds,
                 "acc_maxdiff": float(np.abs(ra - ga).max()),
                 "acc_finite": bool(np.isfinite(ga).all())}
print(json.dumps(out))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 8
    for algo in ALGOS:
        rec = out[algo]
        assert rec["bytes_exact"], (algo, rec)       # layout-independent
        assert rec["sec_exact"], (algo, rec)
        assert rec["acc_finite"], (algo, rec)
        assert rec["acc_maxdiff"] <= 0.1, (algo, rec)


# ----------------------------------------------------- validation ---------
def test_mesh_must_divide_n(tiny_ds):
    with pytest.raises(ValueError, match="divide"):
        run_experiment("el", CFG, tiny_ds, mesh=(3,), **KW)   # n=4


def test_mesh_requires_engine_driver(tiny_ds):
    with pytest.raises(ValueError, match="engine"):
        run_experiment("el", CFG, tiny_ds, mesh=(1,), engine=False, **KW)


def test_normalize_canonicalizes_and_rejects():
    assert meshctx.normalize(None) is None
    assert meshctx.normalize(8) == (8,)
    assert meshctx.normalize((8,)) == (8,)
    assert meshctx.normalize([4]) == (4,)
    with pytest.raises(ValueError, match="one axis"):
        meshctx.normalize((2, 4))
    with pytest.raises(ValueError, match="at least 1"):
        meshctx.normalize((0,))


def test_build_refuses_more_devices_than_visible():
    need = len(jax.devices()) + 1
    # the error names what is visible; the forced-host hint is CPU-only
    with pytest.raises(RuntimeError,
                       match=f"cpu shows {need - 1}; .*device_count"):
        meshctx.build((need,))


# ------------------------------------------------- cache-key forking ------
def test_mesh_is_a_cache_key_axis():
    """A sharded segment program has different layouts and collectives
    than the single-device one — sharded/unsharded specs must never share
    an entry."""
    base = EngineSpec(algo="el", cfg=CFG, n=4, k=2, degree=2,
                      local_steps=2, batch_size=4, lr=0.05)
    meshed = dataclasses.replace(base, mesh=(1,))
    assert base != meshed and hash(base) != hash(meshed)
    cache = EngineCache()
    e_base = cache.entry(base)
    e_mesh = cache.entry(meshed)
    assert cache.misses == 2 and cache.hits == 0
    assert e_base is not e_mesh
    assert e_base.engine is not e_mesh.engine
    assert cache.entry(dataclasses.replace(base, mesh=(1,))) is e_mesh
    assert cache.hits == 1


# ------------------------------------------------- layout-rule units ------
def test_node_spec_rule():
    n = 6
    row = np.zeros((n, 3, 2))
    assert meshctx.node_spec(row, n) == P("node", None, None)
    assert meshctx.node_spec(np.zeros((n,)), n) == P("node")
    assert meshctx.node_spec(np.zeros((n - 1, 3)), n) == P()   # not node-led
    assert meshctx.node_spec(np.float32(0.0), n) == P()        # scalar
    assert meshctx.node_spec(np.zeros((2,)), n) == P()         # PRNG key


def test_launch_helpers_mirror_the_rule():
    from repro.launch.mesh import make_node_mesh
    from repro.launch.shardings import node_carry_specs

    n = 4
    carry = {"params": np.zeros((n, 3)), "mix": np.zeros((n, n)),
             "key": np.zeros((2,), np.uint32), "round": np.int32(0)}
    specs = node_carry_specs(carry, n)
    assert specs["params"] == P("node", None)
    assert specs["mix"] == P("node", None)
    assert specs["key"] == P() and specs["round"] == P()

    mesh = make_node_mesh(1)
    assert mesh.axis_names == (meshctx.NODE_AXIS,)
    assert mesh.size == 1
    # outside any trace context the bindings see no mesh
    assert meshctx.current() is None
    with meshctx.activate(mesh):
        assert meshctx.current() is mesh
    assert meshctx.current() is None
