"""Local SGD on node-packed activations (``bindings.local_sgd_nodes``).

Pins: the CNNs' node-stacked loss on ``[B, H, W, n*C]`` activations and
its gradient equal the per-node ``jax.vmap`` loss and gradient (ResNet8's
stride-2 blocks, projection shortcuts and GroupNorm; GN-LeNet's pools and
flattened FC), for a node count that packs one node to a group (3) and
one that packs several (4); a whole FACADE or EL round on the packed path
equals the same round trained node by node; a node whose state is not
finite poisons no other node; a sequence model keeps the ``node_vmap``
path bit for bit; and the ``compile`` span states the path it built.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.facade_paper import lenet
from repro.core import bindings, facade, split
from repro.core.baselines import ELConfig, el_round
from repro.core.state import BaselineState, FacadeState
from repro.core.cache import CacheEntry, EngineSpec
from repro.models import cnn
from repro.models.base import CNNConfig, get_config
from repro.obs import Tracer

RESNET8 = CNNConfig(name="resnet8-16px", kind="resnet8", image_size=16,
                    width=32, n_classes=10)
LENET = lenet(smoke=True)
CFGS = {"resnet8": RESNET8, "lenet": LENET}


def _stacked(binding, n, seed=0):
    """``n`` node-stacked models for ``binding``, drawn with NumPy at He
    scale (GroupNorm gains near 1)."""
    rng = np.random.default_rng(seed)

    def leaf(path, s):
        noise = rng.normal(size=(n,) + s.shape)
        if jax.tree_util.keystr(path[-1:]) == "['g']":
            return jnp.asarray(1 + 0.1 * noise, s.dtype)
        fan_in = max(1, int(np.prod(s.shape[:-1])))
        return jnp.asarray(noise * np.sqrt(2 / fan_in), s.dtype)

    return jax.tree_util.tree_map_with_path(
        leaf, jax.eval_shape(binding.init, jax.random.PRNGKey(0)))


def _batches(cfg, n, h, b=4, seed=1):
    rng = np.random.default_rng(seed)
    s = cfg.image_size
    return {"x": jnp.asarray(rng.normal(size=(n, h, b, s, s, cfg.channels)),
                             jnp.float32),
            "y": jnp.asarray(rng.integers(0, cfg.n_classes, (n, h, b)),
                             jnp.int32)}


def _close(got, ref, tol=1e-5):
    """float32 agreement, node by node: the packed path sums the same
    products in another order, which moves each leaf of a node by about
    1e-6 of its norm."""
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        if not jnp.issubdtype(g.dtype, jnp.floating) or g.ndim == 0:
            continue
        g = np.asarray(g, np.float64).reshape(len(g), -1)
        r = np.asarray(r, np.float64).reshape(len(r), -1)
        err = np.linalg.norm(g - r, axis=1)
        assert np.all(err <= tol * np.linalg.norm(r, axis=1)), err


@pytest.mark.parametrize("n", [1, 3, 4])
@pytest.mark.parametrize("model", ["resnet8", "lenet"])
def test_packed_loss_and_grad_equal_per_node(model, n):
    cfg = CFGS[model]
    params = _stacked(bindings.make_binding(cfg), n)
    data = jax.tree.map(lambda l: l[:, 0], _batches(cfg, n, 1))

    def per_node(p):
        return jax.vmap(lambda q, x, y: cnn.loss_fn(
            cfg, q, {"x": x, "y": y})[0])(p, data["x"], data["y"]).sum()

    def packed(p):
        return cnn.loss_fn(
            cfg, p, {"x": cnn.pack_nodes(data["x"]), "y": data["y"].T})[0]

    ref, g_ref = jax.jit(jax.value_and_grad(per_node))(params)
    got, g_got = jax.jit(jax.value_and_grad(packed))(params)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
    _close(g_got, g_ref)


def _round(algo, binding, n, h=2):
    params, key = _stacked(binding, n), jax.random.PRNGKey(2)
    if algo == "facade":
        fcfg = facade.FacadeConfig(n_nodes=n, k=2, degree=2, local_steps=h,
                                   lr=0.05)
        cores, head = split.split_params(params, binding.head_keys)
        heads = jax.tree.map(lambda l: jnp.stack([l, 1.01 * l], 1), head)
        state = FacadeState(cores, heads, jnp.zeros((n,), jnp.int32),
                            jnp.int32(0), key)
        fn = lambda s, b: facade.facade_round(fcfg, binding, s, b)  # noqa
    else:
        cfg = ELConfig(n_nodes=n, degree=2, local_steps=h, lr=0.05)
        state = BaselineState(params, None, jnp.int32(0), key)
        fn = lambda s, b: el_round(cfg, binding, s, b)  # noqa
    return jax.jit(fn)(state, _batches(binding.cfg, n, h))[0]


@pytest.mark.parametrize("algo,model", [("facade", "lenet"),
                                        ("el", "resnet8")])
def test_round_on_the_packed_path_equals_node_by_node(algo, model):
    """H = 2 steps. Over more, a ReLU whose input lies within rounding of
    zero can fall on either side in the two orders of summation, and the
    steps after it grow that to about 1% of a node (PERF.md section 6)."""
    binding = bindings.make_binding(CFGS[model])
    assert bindings.sgd_path(binding) == "packed"
    by_node = binding._replace(pack=None)
    assert bindings.sgd_path(by_node) == "vmap"
    _close(_round(algo, binding, 4), _round(algo, by_node, 4))


def test_a_node_that_is_not_finite_poisons_no_other():
    """GN-LeNet at width 4 packs all four nodes into one group."""
    binding = bindings.make_binding(LENET)
    params = _stacked(binding, 4)
    params = jax.tree.map(lambda l: l.at[1].set(jnp.nan), params)
    batches = _batches(LENET, 4, 2)
    got = jax.jit(lambda p, b: bindings.local_sgd_nodes(
        binding, p, b, 0.05))(params, batches)
    ref = jax.jit(bindings.node_vmap(lambda p, b: bindings.local_sgd(
        binding, p, b, 0.05)))(params, batches)
    rest = np.array([0, 2, 3])
    _close(jax.tree.map(lambda l: l[rest], got),
           jax.tree.map(lambda l: l[rest], ref))
    assert all(np.isnan(np.asarray(l[1])).all()
               for l in jax.tree.leaves(got))


def test_sequence_binding_keeps_the_vmap_path_bit_for_bit():
    cfg = get_config("llama3.2-1b", smoke=True)
    binding = bindings.make_binding(cfg)
    assert bindings.sgd_path(binding) == "vmap"
    n, h, b, s = 2, 2, 2, 16
    params = _stacked(binding, n)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (n, h, b, s + 1)), jnp.int32)
    batches = {"tokens": toks[..., :-1], "labels": toks[..., 1:],
               "mask": jnp.ones((n, h, b, s), jnp.float32)}
    got = jax.jit(lambda p, x: bindings.local_sgd_nodes(
        binding, p, x, 1e-2))(params, batches)
    ref = jax.jit(bindings.node_vmap(lambda p, x: bindings.local_sgd(
        binding, p, x, 1e-2)))(params, batches)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_compile_span_states_the_sgd_path(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name, **stats):
            seen.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    spec = EngineSpec(algo="el", cfg=LENET, n=4, k=2, degree=2,
                      local_steps=1, batch_size=4, lr=0.05)
    entry = CacheEntry(spec)
    k_rng, k_data = jax.random.split(jax.random.PRNGKey(0))
    state = BaselineState(_stacked(entry.binding, 4), None, jnp.int32(0),
                          k_rng)
    carry = entry.engine.init_carry(state, k_data)
    data = _batches(LENET, 4, 1, 8)
    tracer = Tracer()
    for _ in range(2):
        carry, outs = entry.engine.dispatch_segment(
            carry, 0, 1, data["x"][:, 0], data["y"][:, 0], tracer=tracer)
        entry.engine.drain(outs, tracer=tracer)
    spans = tracer.rollup()["spans"]
    assert spans["compile"]["sgd_path"] == {"packed": 1}
    assert "sgd_path" not in spans["dispatch"]
    compiles = [st for name, st in seen if name == "repro.compile"]
    assert compiles == [{"length": 1, "warmup": False,
                         "sgd_path": "packed", "model": LENET.name,
                         "pack_groups": "4,4,4", "nodes": 4}]
