"""The published GN-LeNet against the plain reference of the chip benchmark.

``chipbench/reference.py`` imports nothing of the program: it walks the
``layers`` of ``chipbench/configs/gn-lenet.json``. Here that list is
scaled to a size a CPU test holds (16 px, width 8, 4 classes; the
convolutions (w, w, 2w) as published) and compared with the program on
seeded weights: the initial weights leaf for leaf, the forward pass one
node at a time (``NODE``) and node-packed (``PACKED``), the packed loss's
gradient, and a few FACADE rounds of ``run_experiment`` replayed by the
reference on the heads the run chose. At the published size the program
has 89,706 parameters a node, as the reference and the FLOP count say.

Tolerances are float32's: the program and the reference sum the same
products in other orders, which moves a result by about 1e-6 of its
norm. The same reference computed in bfloat16 misses each of them by
orders of magnitude, and a case here says so.
"""
import copy
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.facade_paper import lenet
from repro.core import bindings
from repro.core.runner import run_experiment
from repro.models import cnn

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from chipbench import correct, flops, reference, run, spec, synth  # noqa

PUBLISHED = spec.config("gn-lenet")
SIZE, WIDTH, CLASSES, NODES = 16, 8, 4, 4
HI = jax.lax.Precision.HIGHEST
TOL = 1e-5        # float32 in another order of summation: ~1e-6


def scaled(model, size=SIZE, width=WIDTH, n_classes=CLASSES):
    """The configuration at ``size`` px and base ``width``: each
    convolution's channels scaled by ``width`` over the published width,
    the FC's input the last convolution's map, flattened."""
    m = copy.deepcopy(model)
    f = width / m["width"]
    m.update(image_size=size, width=width, n_classes=n_classes)
    for layer in m["layers"]:
        if layer["op"] == "conv":
            if layer["cin"] != m["channels"]:
                layer["cin"] = int(layer["cin"] * f)
            layer["cout"] = int(layer["cout"] * f)
            last = layer["cout"]
        elif layer["op"] == "dense":
            layer.update(din=(size // 8) ** 2 * last, dout=n_classes)
    return m


MODEL = scaled(PUBLISHED)
CFG = spec.cnn_config(MODEL)


def _rel(got, want):
    """Relative error of ``got`` against ``want``, row by row of the
    leading axis (a node, or one image's logits)."""
    g = np.asarray(got, np.float64).reshape(len(got), -1)
    w = np.asarray(want, np.float64).reshape(len(want), -1)
    return np.linalg.norm(g - w, axis=1) / np.linalg.norm(w, axis=1)


def _ref_logits(model, params, x, prec=HI):
    core, head = reference.split(model, params)
    return reference.head_logits(model, head,
                                 reference.features(model, core, x, prec),
                                 prec)


def _nodes(seed=0):
    """``NODES`` node-stacked models from the reference's own init."""
    keys = jax.random.split(jax.random.PRNGKey(seed), NODES)
    return jax.vmap(lambda k: reference.init_params(MODEL, k))(keys)


def _images(seed=1, b=4):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(NODES, b, SIZE, SIZE, 3)).astype(np.float32)
    y = rng.integers(0, CLASSES, (NODES, b)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def test_lenet_is_the_published_gn_lenet():
    cfg = lenet()
    assert spec.cnn_config(PUBLISHED) == cfg
    shapes = jax.eval_shape(lambda k: cnn.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    assert count == flops.params(PUBLISHED) == \
        reference.param_count(PUBLISHED) == 89_706
    assert [shapes[f"conv{i}"]["w"].shape for i in (1, 2, 3)] == \
        [(5, 5, 3, 32), (5, 5, 32, 32), (5, 5, 32, 64)]
    assert shapes["fc"]["w"].shape == (1024, 10)
    assert cnn.head_keys(cfg) == tuple(PUBLISHED["head"]) == ("fc",)


@pytest.mark.parametrize("seed", [0, 7])
def test_init_equals_the_reference_leaf_for_leaf(seed):
    key = jax.random.PRNGKey(seed)
    got, want = cnn.init_params(CFG, key), reference.init_params(MODEL, key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("layers", ["NODE", "PACKED"])
def test_forward_equals_the_reference(layers):
    params, (x, _) = _nodes(), _images()
    want = jax.vmap(lambda p, xb: _ref_logits(MODEL, p, xb))(params, x)
    with jax.default_matmul_precision("highest"):
        if layers == "NODE":
            got = jax.vmap(lambda p, xb: cnn.forward(CFG, p, xb))(params, x)
        else:
            got = cnn.forward(CFG, params, cnn.pack_nodes(x), cnn.PACKED)
    assert got.shape == (NODES, x.shape[1], CLASSES)
    assert _rel(got, want).max() <= TOL


def test_packed_gradient_equals_each_nodes_reference_gradient():
    params, (x, y) = _nodes(), _images()

    def ref_loss(p, xb, yb):
        return reference.xent(_ref_logits(MODEL, p, xb), yb)

    want = jax.vmap(jax.grad(ref_loss))(params, x, y)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: cnn.loss_fn(
            CFG, p, {"x": cnn.pack_nodes(x), "y": y.T})[0])(params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _rel(g, w).max() <= TOL


def test_bfloat16_reference_misses_the_tolerance():
    """The tolerance is tight enough that the bfloat16 control fails it."""
    params, (x, _) = _nodes(), _images()
    want = jax.vmap(lambda p, xb: _ref_logits(MODEL, p, xb))(params, x)
    low = jax.tree.map(lambda l: l.astype(jnp.bfloat16), params)
    got = jax.vmap(lambda p, xb: _ref_logits(MODEL, p, xb, None))(
        low, x.astype(jnp.bfloat16))
    assert _rel(got, want).min() > 10 * TOL


CELL = {"name": "gn-lenet-cpu", "config": "gn-lenet", "chips": 1,
        "algo": "facade", "clusters": [4, 2], "transforms": ["rot0", "rot180"],
        "train_per_class": 4, "test_per_class": 8, "noise": 0.35, "jitter": 2,
        "degree": 4, "local_steps": 2, "batch_size": 4, "lr": 0.05,
        "eval_every": 2, "rounds_per_run": 2, "eval_batch": 16, "mesh": None,
        "model": MODEL}


def test_facade_rounds_equal_the_reference_on_the_runs_heads():
    """Two rounds of two SGD steps on the packed path, six nodes, two
    clusters, ending with the final all-reduce; the reference replays
    them from the seed. Over many more steps a ReLU input within rounding
    of zero can fall either way and part the trajectories (PERF.md,
    section 2): two rounds stay at float32's rounding."""
    seed, data_seed = 11, 5
    assert bindings.sgd_path(bindings.make_binding(CFG)) == "packed"
    ds = synth.make_dataset(CELL, data_seed)
    cache = run.tapped_cache()
    res = run_experiment("facade", CFG, ds, rounds=CELL["rounds_per_run"],
                         seed=seed, **run.experiment_kwargs(CELL, cache))
    seg = run.checked_segment(CELL, res, cache.tap)
    got = correct.compare(reference.setup(CELL), ds, seed=seed,
                          select_margin=1e-4, **seg)
    assert got["bytes_gap"] == 0
    assert got["select_miss"] == 0
    assert got["pred_gap"] == 0 and got["acc_gap"] == 0
    # float32 reads about 2e-6 here; bfloat16 parts by 1e-3 or more
    assert got["model_drift"] <= 2e-5, got["model_drift"]
