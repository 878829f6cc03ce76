"""Plain reference of FACADE (arXiv:2410.02541, Sec. III-D) on the paper's
CNNs, written from the paper and the configuration files alone: the model
is the configuration's ``layers`` list, walked layer by layer.

It imports nothing of the program. Each node's model is a dict of arrays
with a leading node axis; one round is:

1. a random 4-regular topology, the union of two random cycles;
2. core mixing with uniform weights over neighbours and self (Eq. 3), and
   per cluster slot the average of the node's own stored head with the
   heads its neighbours sent for that slot (Eq. 4);
3. cluster identification: the head of least loss on the round's first
   local batch, over the shared core features;
4. H steps of plain SGD on (core, chosen head), written back to the slot.

The randomness (initial weights, batch draws, topology) follows the
documented seeding of ``run_experiment``: ``PRNGKey(seed)`` split into an
init key and a data key, the init key split three ways (weights, head
jitter, topology), the data key split once per round. Matmuls and
convolutions run at ``Precision.HIGHEST`` in float32.

``run`` can be *forced*: given the head each node chose in each round, it
follows those choices and reports, for every round and node, by how much
the chosen head's loss lies above the best one's (the greedy-decode check
of a served model). That keeps a reference trajectory aligned with a run
whose near-tied choices fell the other way on rounding.

``dtype="bfloat16"`` computes everything in bfloat16 at default precision:
the lower-precision control. ``fault`` plants one of the faults the
correctness check has to catch.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PARAM_OPS = ("conv", "block", "dense")       # layer ops with parameters
FAULTS = ("frozen", "half_batch", "inverted", "bad_answer")


class Setup(NamedTuple):
    """Static description of a run; hashable, so it keys the jitted
    round. ``model_items``: the configuration file's entries, frozen into
    tuples by :func:`setup`."""
    model_items: tuple
    n: int
    k: int
    degree: int
    local_steps: int
    batch: int
    lr: float
    dtype: str = "float32"
    fault: str | None = None

    @property
    def model(self) -> dict:
        return _thaw(self.model_items)

    @property
    def prec(self):
        return HI if self.dtype == "float32" else None

    @property
    def dt(self):
        return jnp.dtype(self.dtype)


# --------------------------------------------------------------------------
# the models
def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _conv_w(key, kh, kw, cin, cout):
    return _normal(key, (kh, kw, cin, cout), jnp.sqrt(2.0 / (kh * kw * cin)))


def _gn(c):
    return {"g": jnp.ones((c,), jnp.float32),
            "b": jnp.zeros((c,), jnp.float32)}


def _dense(key, d_in, d_out):
    return {"w": _normal(key, (d_in, d_out), 1.0 / jnp.sqrt(d_in)),
            "b": jnp.zeros((d_out,), jnp.float32)}


def _block_init(key, cin, cout):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"conv1": _conv_w(k1, 3, 3, cin, cout), "gn1": _gn(cout),
         "conv2": _conv_w(k2, 3, 3, cout, cout), "gn2": _gn(cout)}
    if cin != cout:
        p["proj"] = _conv_w(k3, 1, 1, cin, cout)
    return p


def _layer_init(key, layer):
    op = layer["op"]
    if op == "conv":
        k, c = layer["k"], layer["cout"]
        return {"w": _conv_w(key, k, k, layer["cin"], c), "gn": _gn(c)}
    if op == "block":
        return _block_init(key, layer["cin"], layer["cout"])
    return _dense(key, layer["din"], layer["dout"])


def init_params(model: dict, key) -> dict:
    """One node's parameters, walking the configuration's ``layers``: one
    key per layer that has parameters, in order."""
    own = [l for l in model["layers"] if l["op"] in PARAM_OPS]
    ks = jax.random.split(key, len(own))
    return {l["name"]: _layer_init(k, l) for k, l in zip(ks, own)}


def _conv(x, w, prec, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)


def _group_norm(x, p, groups, eps=1e-5):
    """Normalise over (H, W, channels of the group), then scale and shift;
    statistics in float32 whatever the activations' type."""
    b, h, w, c = x.shape
    g = x.astype(jnp.float32).reshape(b, h, w, groups, c // groups)
    mu = g.mean(axis=(1, 2, 4), keepdims=True)
    var = ((g - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    y = ((g - mu) / jnp.sqrt(var + eps)).reshape(x.shape)
    return (y * p["g"].astype(jnp.float32)
            + p["b"].astype(jnp.float32)).astype(x.dtype)


def _pool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def _resblock(p, x, stride, groups, prec):
    h = jax.nn.relu(_group_norm(_conv(x, p["conv1"], prec, stride),
                                p["gn1"], groups))
    h = _group_norm(_conv(h, p["conv2"], prec), p["gn2"], groups)
    if "proj" in p:
        x = _conv(x, p["proj"], prec, stride)
    elif stride != 1:
        x = x[:, ::stride, ::stride]
    return jax.nn.relu(h + x)


def _apply(layer, p, x, groups, prec):
    """One entry of ``layers``: ``conv`` (convolution, GroupNorm, ReLU and,
    with ``pool``, a 2x2 max-pool), ``block`` (a basic residual block),
    ``avgpool`` (global mean), ``flatten`` or ``dense``."""
    op = layer["op"]
    if op == "conv":
        x = jax.nn.relu(_group_norm(
            _conv(x, p["w"], prec, layer["stride"]), p["gn"], groups))
        return _pool(x) if layer.get("pool") else x
    if op == "block":
        return _resblock(p, x, layer["stride"], groups, prec)
    if op == "avgpool":
        return x.mean(axis=(1, 2))
    if op == "flatten":
        return x.reshape(x.shape[0], -1)
    if op == "dense":
        return jnp.dot(x, p["w"], precision=prec) + p["b"]
    raise ValueError(f"unknown layer op {op!r}")


def _parts(model):
    """The layers of the core and of the FACADE head: the head starts at
    the first layer it names."""
    layers = model["layers"]
    first = min(i for i, l in enumerate(layers) if l["name"] in model["head"])
    return layers[:first], layers[first:]


def _walk(model, params, x, layers, prec):
    for l in layers:
        x = _apply(l, params.get(l["name"]), x, model["groups"], prec)
    return x


def head_keys(model: dict) -> tuple:
    return tuple(model["head"])


def features(model, core, x, prec):
    """The core: everything below the FACADE head."""
    return _walk(model, core, x, _parts(model)[0], prec)


def head_logits(model, head, feats, prec):
    return _walk(model, head, feats, _parts(model)[1], prec)


def xent(logits, y):
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    return (lse - jnp.take_along_axis(lf, y[:, None], axis=-1)[:, 0]).mean()


def split(model, params):
    hk = head_keys(model)
    return ({k: v for k, v in params.items() if k not in hk},
            {k: v for k, v in params.items() if k in hk})


def param_count(model) -> int:
    shapes = jax.eval_shape(lambda k: init_params(model, k),
                            jax.random.PRNGKey(0))
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))


def round_bytes(model, n: int, degree: int) -> float:
    """Bytes one round sends on an ideal medium: ``n * degree`` pushes of
    a float32 model plus FACADE's int32 cluster id, as a float32."""
    return float(np.float32(n * degree * (4 * param_count(model) + 4)))


# --------------------------------------------------------------------------
# one round
def _topology(key, n):
    """Union of two random cycles: symmetric 0/1, zero diagonal."""
    a = jnp.zeros((n, n), jnp.float32)
    keys = jax.random.split(key, 3)
    for i in range(2):
        perm = jax.random.permutation(keys[i], n)
        nxt = jnp.roll(perm, 1)
        a = a.at[perm, nxt].set(1.0).at[nxt, perm].set(1.0)
    return a * (1.0 - jnp.eye(n))


def _mix(s: Setup, w, tree):
    return jax.tree.map(
        lambda l: jnp.einsum("ij,j...->i...", w.astype(l.dtype), l,
                             precision=s.prec).astype(l.dtype), tree)


def _aggregate_heads(s: Setup, adj, cid, heads):
    """Eq. 4: slot c of node i averages i's own stored head c with the
    heads sent by the neighbours that claim cluster c."""
    onehot = jax.nn.one_hot(cid, s.k, dtype=jnp.float32)           # [n, k]
    denom = 1.0 + jnp.einsum("ij,jc->ic", adj, onehot, precision=HI)

    def agg(h):
        sent = h[jnp.arange(s.n), cid]                               # [n,...]
        recv = jnp.einsum("ij,jc,j...->ic...", adj.astype(h.dtype),
                          onehot.astype(h.dtype), sent, precision=s.prec)
        d = denom.reshape(denom.shape + (1,) * (h.ndim - 2))
        return ((h + recv) / d.astype(h.dtype)).astype(h.dtype)

    return jax.tree.map(agg, heads)


def _loss(s: Setup, model, params, x, y):
    if s.fault == "half_batch":
        x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
    core, head = split(model, params)
    return xent(head_logits(model, head, features(model, core, x, s.prec),
                            s.prec), y)


def _local_sgd(s: Setup, model, params, xs, ys):
    def step(p, xy):
        g = jax.grad(functools.partial(_loss, s, model))(p, *xy)
        if s.fault == "frozen":
            return p, None
        return jax.tree.map(lambda w, gg: (w - s.lr * gg).astype(w.dtype),
                            p, g), None

    return jax.lax.scan(step, params, (xs, ys))[0]


@functools.partial(jax.jit, static_argnums=(0,))
def _round(s: Setup, state, k_data, train_x, train_y, forced):
    """One round for all nodes. ``forced`` [n] int32 (< 0: choose freely).
    Returns the new state and data key, the chosen heads and how far each
    chosen head's loss lies above the best. With the ``inverted`` fault a
    free choice takes the head of greatest loss."""
    model = s.model
    cores, heads, cid, rng = state
    rng, k_topo = jax.random.split(rng)
    k_data, k_b = jax.random.split(k_data)
    n, per_node = train_x.shape[:2]
    idx = jax.random.randint(k_b, (n, s.local_steps, s.batch), 0, per_node)
    bx = jax.vmap(lambda x, i: x[i])(train_x, idx).astype(s.dt)
    by = jax.vmap(lambda y, i: y[i])(train_y, idx)

    adj = _topology(k_topo, n)
    a_hat = adj + jnp.eye(n)
    w = a_hat / a_hat.sum(axis=1, keepdims=True)
    cores = _mix(s, w, cores)
    heads = _aggregate_heads(s, adj, cid, heads)

    def select(core, heads_k, x, y):
        f = features(model, core, x, s.prec)
        return jax.vmap(lambda h: xent(head_logits(model, h, f, s.prec),
                                       y))(heads_k)

    losses = jax.vmap(select)(cores, heads, bx[:, 0], by[:, 0])     # [n, k]
    pick = jnp.argmax if s.fault == "inverted" else jnp.argmin
    own = pick(losses, axis=1).astype(jnp.int32)
    new_cid = jnp.where(forced >= 0, forced, own)
    excess = jnp.take_along_axis(losses, new_cid[:, None], 1)[:, 0] \
        - losses.min(axis=1)

    def train(core, heads_k, c, xs, ys):
        head = jax.tree.map(lambda h: h[c], heads_k)
        p = _local_sgd(s, model, {**core, **head}, xs, ys)
        new_core, new_head = split(model, p)
        heads_k = jax.tree.map(lambda hk, h: hk.at[c].set(h.astype(hk.dtype)),
                               heads_k, new_head)
        return new_core, heads_k

    cores, heads = jax.vmap(train)(cores, heads, new_cid, bx, by)
    return (cores, heads, new_cid, rng), k_data, new_cid, excess


@functools.partial(jax.jit, static_argnums=(0,))
def _final_allreduce(s: Setup, state):
    """Sec. V-A: every node shares with every other one, cluster-wise."""
    cores, heads, cid, rng = state
    adj = 1.0 - jnp.eye(s.n)
    a_hat = adj + jnp.eye(s.n)
    w = a_hat / a_hat.sum(axis=1, keepdims=True)
    return (_mix(s, w, cores), _aggregate_heads(s, adj, cid, heads), cid, rng)


def node_models(state):
    cores, heads, cid, _ = state
    pick = jax.tree.map(lambda h: h[jnp.arange(h.shape[0]), cid], heads)
    return {**cores, **pick}


# --------------------------------------------------------------------------
def setup(cell: dict, dtype: str = "float32", fault: str | None = None
          ) -> Setup:
    """The reference's description of a cell's runs (FACADE's)."""
    if cell["algo"] != "facade":
        raise ValueError(f"the reference runs FACADE, not {cell['algo']!r}")
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    return Setup(_freeze(cell["model"]), n=sum(cell["clusters"]),
                 k=len(cell["clusters"]), degree=cell["degree"],
                 local_steps=cell["local_steps"], batch=cell["batch_size"],
                 lr=cell["lr"], dtype=dtype, fault=fault)


def _freeze(v):
    """JSON values as hashable tuples: a dict as ("dict", items)."""
    if isinstance(v, dict):
        return ("dict", tuple(sorted((k, _freeze(x)) for k, x in v.items())))
    if isinstance(v, list):
        return tuple(_freeze(x) for x in v)
    return v


def _thaw(v):
    if isinstance(v, tuple) and v[:1] == ("dict",):
        return {k: _thaw(x) for k, x in v[1]}
    if isinstance(v, tuple):
        return [_thaw(x) for x in v]
    return v


def init(s: Setup, seed: int):
    """(state, data key) of ``run_experiment(seed=seed)``'s first round."""
    k_init, k_data = jax.random.split(jax.random.PRNGKey(seed))
    k_w, _, k_rng = jax.random.split(k_init, 3)
    params = jax.tree.map(lambda l: l.astype(s.dt),
                          init_params(s.model, k_w))
    core, head = split(s.model, params)
    rep = lambda t, shape: jax.tree.map(                        # noqa: E731
        lambda l: jnp.broadcast_to(l, shape + l.shape), t)
    heads = rep(head, (s.k,))
    state = (rep(core, (s.n,)), rep(heads, (s.n,)),
             jnp.zeros((s.n,), jnp.int32), k_rng)
    return state, k_data


class Result(NamedTuple):
    models: dict          # node models after the last round, [n, ...]
    init: dict            # the initial model (one node's)
    cids: np.ndarray      # [rounds, n] heads chosen
    excess: np.ndarray    # [rounds, n] chosen head's loss above the least
    bytes: float          # bytes sent over the rounds


def run(s: Setup, seed: int, train_x, train_y, rounds: int, *,
        final: bool, forced=None) -> Result:
    """``rounds`` rounds from ``seed``; ``final``: end with the final
    all-reduce (the experiment's last round). ``forced`` [rounds, n]."""
    state, k_data = init(s, seed)
    init_model = jax.tree.map(lambda l: l[0], node_models(state))
    tx, ty = jnp.asarray(train_x), jnp.asarray(train_y)
    cids, excess = [], []
    for r in range(rounds):
        f = (jnp.full((s.n,), -1, jnp.int32) if forced is None
             else jnp.asarray(forced[r], jnp.int32))
        state, k_data, cid, e = _round(s, state, k_data, tx, ty, f)
        cids.append(cid)
        excess.append(e)
    if final:
        state = _final_allreduce(s, state)
    return Result(node_models(state), init_model,
                  np.asarray(jnp.stack(cids)), np.asarray(jnp.stack(excess)),
                  rounds * round_bytes(s.model, s.n, s.degree))


@functools.partial(jax.jit, static_argnums=(0,))
def _predict(s: Setup, models, x):
    """[m nodes] x [B images] -> predicted classes [m, B]."""
    model = s.model

    def one(p):
        core, head = split(model, p)
        return jnp.argmax(head_logits(model, head,
                                      features(model, core, x, s.prec),
                                      s.prec), -1)

    return jax.vmap(one)(models)


def predictions(s: Setup, models, node_cluster, test_x,
                batch: int = 250) -> list[np.ndarray]:
    """Per cluster, the class each of the cluster's nodes predicts for
    each image of the cluster's test set, [m, M], in blocks of ``batch``
    images. With the ``bad_answer`` fault every prediction is moved on by
    one class."""
    node_cluster = np.asarray(node_cluster)
    out = []
    for c, x in enumerate(test_x):
        idx = np.where(node_cluster == c)[0]
        if idx.size == 0:
            continue
        mc = jax.tree.map(lambda l: jnp.asarray(l)[idx], models)
        p = np.concatenate([np.asarray(_predict(s, mc, jnp.asarray(
            x[lo:lo + batch], s.dt))) for lo in range(0, x.shape[0], batch)],
            axis=1)
        if s.fault == "bad_answer":
            p = (p + 1) % s.model["n_classes"]
        out.append(p)
    return out
