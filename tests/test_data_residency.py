"""The train split stays on the device across ``run_experiment`` calls.

Pins: an :class:`EngineCache` keeps ONE dataset's train split on the
device, keyed on the dataset object (held weakly), the identity of its
``train_x`` / ``train_y`` arrays and the node-mesh shape. A second run
over the same dataset moves 0 bytes (the ``upload`` span's ``bytes``,
``data_hits`` / ``data_uploads``, the ``data.hit`` / ``data.miss`` events)
and gives the fresh-cache run bit for bit under both drivers, the engine
checkpointed or not; a reassigned array, another dataset or another
placement uploads again, releasing the old arrays first; deleting the
dataset frees them; and a ``ClusteredDataset`` is hashed by identity, so
``data_fingerprint`` hashes its content once.
"""
import dataclasses
import gc

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from repro.configs.facade_paper import lenet
from repro.core.cache import EngineCache, EngineSpec
from repro.core.engine import SegmentEngine
from repro.core.runner import run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.obs import Obs

pytestmark = pytest.mark.tier0

CFG = lenet(smoke=True).replace(n_classes=4)
KW = dict(rounds=2, k=2, degree=2, local_steps=2, batch_size=4, lr=0.05,
          eval_every=1)
DRIVERS = ("engine", "checkpointed", "legacy")
# 7 samples a class: a train shape [n, 28, 16, 16, 3] no other test makes,
# so jax.live_arrays() finds only this file's train arrays by shape
PER_CLASS = 7


def _dataset(seed: int = 3):
    spec = SynthSpec(n_classes=4, image_size=16,
                     samples_per_class=PER_CLASS, test_per_class=8,
                     seed=seed)
    return make_clustered_data(spec, cluster_sizes=(3, 1),
                               transforms=("rot0", "rot180"))


def _train_shaped(shape) -> list:
    """The live device arrays of the train images' ``shape``."""
    return [a for a in jax.live_arrays() if a.shape == shape]


def _assert_runs_identical(ref, got):
    assert ref.acc_per_cluster == got.acc_per_cluster
    assert ref.fair_acc == got.fair_acc
    assert ref.dp == got.dp and ref.eo == got.eo
    assert ref.final_acc == got.final_acc
    assert ref.comm.rounds == got.comm.rounds
    assert ref.comm.bytes == got.comm.bytes
    assert ref.comm.seconds == got.comm.seconds
    assert len(ref.cluster_history) == len(got.cluster_history)
    for (r1, c1), (r2, c2) in zip(ref.cluster_history, got.cluster_history):
        assert r1 == r2
        np.testing.assert_array_equal(c1, c2)


def _driver(driver: str, tmp_path, run: str) -> dict:
    """``run_experiment`` arguments for ``driver``; a checkpointed run
    writes its own archive, named by ``run``."""
    if driver == "legacy":
        return {"engine": False}
    if driver == "checkpointed":
        return {"ckpt": str(tmp_path / f"{run}.npz")}
    return {}


def _upload(obs) -> dict:
    return obs.tracer.rollup()["spans"]["upload"]


# ------------------------------------------------------------- hits --
@pytest.mark.parametrize("driver", DRIVERS)
def test_a_second_run_over_one_dataset_moves_no_bytes(driver, tmp_path):
    ds, cache = _dataset(), EngineCache()
    first, second = Obs(None, health=None), Obs(None, health=None)
    run_experiment("facade", CFG, ds, cache=cache, obs=first, seed=0,
                   **KW, **_driver(driver, tmp_path, "first"))
    assert _upload(first)["bytes"] == ds.train_x.nbytes + ds.train_y.nbytes
    assert first.tracer.rollup()["events"]["data.miss"] == 1
    run_experiment("facade", CFG, ds, cache=cache, obs=second, seed=1,
                   **KW, **_driver(driver, tmp_path, "second"))
    assert _upload(second)["count"] == 1 and _upload(second)["bytes"] == 0
    events = second.tracer.rollup()["events"]
    assert events["data.hit"] == 1 and "data.miss" not in events
    st = cache.stats()
    assert st["data_hits"] == 1 and st["data_uploads"] == 1
    assert second.manifests[-1].cache["data_hits"] == 1


@pytest.mark.parametrize("driver", DRIVERS)
def test_a_kept_train_split_gives_the_fresh_run_bit_for_bit(driver,
                                                            tmp_path):
    """Only the carry is donated: a run over the kept train arrays (after
    another run read them) is the fresh-cache run of its seed exactly."""
    ds, cache = _dataset(), EngineCache()
    run_experiment("facade", CFG, ds, cache=cache, seed=5, **KW,
                   **_driver(driver, tmp_path, "other"))
    got = run_experiment("facade", CFG, ds, cache=cache, seed=6, **KW,
                         **_driver(driver, tmp_path, "kept"))
    assert cache.data_hits == 1
    ref = run_experiment("facade", CFG, ds, cache=EngineCache(), seed=6,
                         **KW, **_driver(driver, tmp_path, "fresh"))
    _assert_runs_identical(ref, got)


def test_a_hit_reads_zero_bytes_on_the_profiler_clock(monkeypatch):
    ds, cache = _dataset(), EngineCache()
    run_experiment("el", CFG, ds, cache=cache, seed=0, **KW)
    seen = []

    class Annotation:
        def __init__(self, name, **stats):
            seen.append((name, stats))

        def __enter__(self):
            pass

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    run_experiment("el", CFG, ds, cache=cache, seed=1, **KW)
    assert [s for n, s in seen if n == "repro.upload"] == [{"bytes": 0}]


# ----------------------------------------------------------- misses --
@pytest.mark.parametrize("field", ["train_x", "train_y"])
def test_a_reassigned_train_array_is_uploaded_again(field):
    ds, cache = _dataset(), EngineCache()
    run_experiment("el", CFG, ds, cache=cache, seed=0, **KW)
    setattr(ds, field, getattr(ds, field).copy())
    obs = Obs(None, health=None)
    got = run_experiment("el", CFG, ds, cache=cache, obs=obs, seed=1, **KW)
    assert _upload(obs)["bytes"] == ds.train_x.nbytes + ds.train_y.nbytes
    assert cache.data_uploads == 2 and cache.data_hits == 0
    assert obs.tracer.rollup()["events"]["data.miss"] == 1
    _assert_runs_identical(
        run_experiment("el", CFG, ds, seed=1, **KW), got)


def test_a_second_dataset_replaces_the_slot(monkeypatch):
    """The old train set is released BEFORE the new one is placed: at no
    point are two train-shaped arrays alive."""
    a, b, cache = _dataset(3), _dataset(4), EngineCache()
    alive_at_place = []
    place = SegmentEngine.place_data

    def counting(self, train_x, train_y):
        alive_at_place.append(len(_train_shaped(a.train_x.shape)))
        return place(self, train_x, train_y)

    monkeypatch.setattr(SegmentEngine, "place_data", counting)
    for ds in (a, b, a):
        run_experiment("el", CFG, ds, cache=cache, seed=0, **KW)
        gc.collect()
        assert len(_train_shaped(ds.train_x.shape)) == 1
    assert alive_at_place == [1, 1, 1]      # the new array alone
    assert cache.data_uploads == 3 and cache.data_hits == 0


def test_deleting_the_dataset_frees_its_arrays():
    ds, cache = _dataset(), EngineCache()
    run_experiment("el", CFG, ds, cache=cache, seed=0, **KW)
    shape = ds.train_x.shape
    gc.collect()
    assert len(_train_shaped(shape)) == 1
    del ds
    gc.collect()
    assert _train_shaped(shape) == []
    assert len(cache._train) == 0


def test_mesh_and_no_mesh_runs_never_share_a_slot():
    ds, cache = _dataset(), EngineCache()
    base = EngineSpec(algo="el", cfg=CFG, n=ds.n_nodes, k=2, degree=2,
                      local_steps=2, batch_size=4, lr=0.05)
    plain = cache.entry(base)
    meshed = cache.entry(dataclasses.replace(base, mesh=(1,)))
    x0, _ = cache.train_data(plain, ds)
    x1, _ = cache.train_data(meshed, ds)
    assert cache.data_uploads == 2 and cache.data_hits == 0
    assert x1 is not x0 and isinstance(x1.sharding, NamedSharding)
    assert cache.train_data(meshed, ds)[0] is x1
    x2, _ = cache.train_data(plain, ds)
    assert not isinstance(x2.sharding, NamedSharding)
    assert cache.data_uploads == 3 and cache.data_hits == 1
    # and through run_experiment: the mesh run uploads its own copy
    run_experiment("el", CFG, ds, cache=cache, seed=0, mesh=(1,), **KW)
    assert cache.data_uploads == 4


def test_an_unhashable_dataset_is_uploaded_every_call():
    """A dataset type that compares by value cannot key the weak slot: it
    runs as before, uploading on every call."""
    @dataclasses.dataclass
    class ByValue:
        train_x: np.ndarray
        train_y: np.ndarray
        test_x: list
        test_y: list
        node_cluster: np.ndarray
        n_nodes: int
        k: int

    src = _dataset()
    ds = ByValue(src.train_x, src.train_y, src.test_x, src.test_y,
                 src.node_cluster, src.n_nodes, src.k)
    cache = EngineCache()
    for seed in (0, 1):
        run_experiment("el", CFG, ds, cache=cache, seed=seed, **KW)
    assert cache.data_uploads == 2 and cache.data_hits == 0
    assert len(cache._train) == 0
