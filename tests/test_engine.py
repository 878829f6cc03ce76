"""Scan-fused segment engine (core/engine.py): bit-for-bit parity with the
legacy per-round driver for all 5 algorithms, with and without netsim; the
FACADE warmup->main segment boundary; segment planning; bulk CommLog
recording; and the vmapped padded evaluator."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.accounting import CommLog
from repro.configs.facade_paper import lenet
from repro.core import runner
from repro.core.cache import EngineCache
from repro.core.engine import Segment, SegmentEngine, segment_plan
from repro.core.runner import algo_setup, make_evaluator, run_experiment
from repro.core.bindings import make_binding
from repro.core.state import EngineCarry
from repro.data import pipeline
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.netsim import NetworkConfig

CFG = lenet(smoke=True).replace(n_classes=4)
ALGOS = ("facade", "el", "dpsgd", "deprl", "dac")


@pytest.fixture(scope="module")
def tiny_ds():
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=8,
                     test_per_class=8, seed=3)
    return make_clustered_data(spec, cluster_sizes=(3, 1),
                               transforms=("rot0", "rot180"))


def _assert_runs_identical(ref, eng):
    assert ref.acc_per_cluster == eng.acc_per_cluster
    assert ref.fair_acc == eng.fair_acc
    assert ref.dp == eng.dp and ref.eo == eng.eo
    assert ref.final_acc == eng.final_acc
    assert ref.comm.rounds == eng.comm.rounds
    assert ref.comm.bytes == eng.comm.bytes          # exact float equality
    assert ref.comm.seconds == eng.comm.seconds
    assert ref.comm.evaled == eng.comm.evaled
    assert len(ref.cluster_history) == len(eng.cluster_history)
    for (r1, c1), (r2, c2) in zip(ref.cluster_history, eng.cluster_history):
        assert r1 == r2
        np.testing.assert_array_equal(c1, c2)


# ------------------------------------------------------------- parity ----
@pytest.mark.parametrize("netname", [None, "edge-churn", "edge-v2"],
                         ids=["ideal", "edge-churn", "edge-v2"])
@pytest.mark.parametrize("algo", ALGOS)
def test_engine_matches_legacy_bitforbit(algo, netname, tiny_ds):
    """rounds=5, eval_every=2 exercises full spans AND a trailing partial
    segment; edge-churn exercises in-scan conditions + the timing model;
    edge-v2 exercises all three netsim-v2 axes at once — the bursty
    channel state and async staleness buffers carried through the scan
    (vs threaded through the legacy Python loop) plus the heterogeneous
    link matrices in the in-scan timing feed."""
    kw = dict(rounds=5, k=2, degree=2, local_steps=2, batch_size=4,
              lr=0.05, eval_every=2, seed=0,
              net=NetworkConfig.preset(netname) if netname else None)
    ref = run_experiment(algo, CFG, tiny_ds, engine=False, **kw)
    eng = run_experiment(algo, CFG, tiny_ds, engine=True, **kw)
    _assert_runs_identical(ref, eng)


def test_facade_warmup_boundary_parity(tiny_ds):
    """Warmup->main switch mid-run: the engine must cut the segment at the
    boundary (two compiled variants), matching the legacy per-round branch
    bit for bit — including a boundary that falls inside an eval span."""
    kw = dict(rounds=6, k=2, degree=2, local_steps=2, batch_size=4,
              lr=0.05, eval_every=4, seed=0, warmup_rounds=3)
    ref = run_experiment("facade", CFG, tiny_ds, engine=False, **kw)
    eng = run_experiment("facade", CFG, tiny_ds, engine=True, **kw)
    _assert_runs_identical(ref, eng)


@pytest.mark.parametrize("hit", ["first", "later"])
def test_target_acc_stops_at_same_round(hit, tiny_ds):
    """target_acc early exit now fires at segment granularity — the same
    eval rounds the legacy driver checked, so both stop identically. The
    engine has already dispatched the segment after the hit; it is
    dropped undrained. ``later``: the target is the first eval's mean
    accuracy that beats every earlier one, so the hit comes after
    segments have been dispatched, drained and evaluated."""
    kw = dict(rounds=8, k=2, degree=2, local_steps=2, batch_size=4,
              lr=0.05, eval_every=2, seed=0)
    stop = 2
    if hit == "first":
        kw["target_acc"] = 0.0
    else:
        full = run_experiment("el", CFG, tiny_ds, engine=False, **kw).comm
        evals = [(r, a) for r, a, e in zip(full.rounds, full.acc,
                                           full.evaled) if e]
        stop, target = next((r, a) for j, (r, a) in enumerate(evals)
                            if j > 0 and a > max(b for _, b in evals[:j]))
        assert stop < kw["rounds"]           # a segment follows the hit
        kw["target_acc"] = target
    ref = run_experiment("el", CFG, tiny_ds, engine=False, **kw)
    eng = run_experiment("el", CFG, tiny_ds, engine=True, **kw)
    _assert_runs_identical(ref, eng)
    assert ref.comm.rounds[-1] == stop


def test_engine_final_state_matches_python_loop(tiny_ds):
    """State-level bit parity: drive SegmentEngine directly vs a hand
    Python loop over the same stepper, and compare every leaf."""
    binding = make_binding(CFG)
    n = tiny_ds.n_nodes
    train_x = jnp.asarray(tiny_ds.train_x)
    train_y = jnp.asarray(tiny_ds.train_y)
    key = jax.random.PRNGKey(0)
    k_init, k_data = jax.random.split(key)
    kw = dict(degree=2, local_steps=2, lr=0.05)

    setup = algo_setup("el", binding, k_init, n, 2, **kw)
    state, kd = setup.state, k_data
    for rnd in range(4):
        kd, kb = jax.random.split(kd)
        batches = pipeline.sample_round_batches(kb, train_x, train_y, 2, 4)
        state, _ = setup.round_fn(state, batches, net=None)

    setup2 = algo_setup("el", binding, k_init, n, 2, **kw)
    eng = SegmentEngine(setup2.round_fn, n=n, local_steps=2, batch_size=4)
    carry = EngineCarry(setup2.state, k_data)
    carry, outs = eng.dispatch_segment(carry, 0, 4, train_x, train_y)
    eng.drain(outs)

    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(carry.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(carry.k_data))


# ------------------------------------------------------------ loop order --
ORDER_PLANS = {
    "one-segment": dict(algo="el", rounds=2, eval_every=2),
    "three-segments": dict(algo="el", rounds=6, eval_every=2),
    "warmup-cut": dict(algo="facade", rounds=6, eval_every=4,
                       warmup_rounds=3),
    "three-segments-ckpt": dict(algo="el", rounds=6, eval_every=2,
                                ckpt=True),
    "hit-at-first-eval": dict(algo="el", rounds=6, eval_every=2,
                              target_acc=0.0),
}


@pytest.mark.parametrize("name", list(ORDER_PLANS))
def test_engine_loop_order(name, tiny_ds, tmp_path, monkeypatch):
    """Segment ``t``'s eval is enqueued before ``t+1`` is dispatched, and
    ``t+1`` is dispatched before ``t`` is drained; under ``ckpt`` the
    carry snapshot is taken before that dispatch (it donates the carry);
    a ``target_acc`` hit leaves exactly one dispatched segment
    undrained."""
    kw = dict(ORDER_PLANS[name])
    algo, ckpt = kw.pop("algo"), kw.pop("ckpt", False)
    if ckpt:
        kw["ckpt"] = str(tmp_path / "run.npz")
    plan = segment_plan(kw["rounds"], kw["eval_every"],
                        kw.get("warmup_rounds", 0))
    evals = [t for t, seg in enumerate(plan) if seg.eval_at_end]
    starts = [seg.start for seg in plan]
    log = []                  # (kind, segment index), in call order

    def count(kind):
        return sum(k == kind for k, _ in log)

    cache = EngineCache()
    evaluator = cache.evaluator(make_binding(CFG), tiny_ds)
    begin, dispatch, drain = (evaluator.begin, SegmentEngine.dispatch_segment,
                              SegmentEngine.drain)
    snapshot = runner._snapshot

    def spy_begin(models):
        log.append(("eval", evals[count("eval")]))
        return begin(models)

    def spy_dispatch(self, carry, start, *a, **k):
        log.append(("dispatch", starts.index(start)))
        return dispatch(self, carry, start, *a, **k)

    def spy_drain(self, outs, *a, **k):
        log.append(("drain", count("drain")))
        return drain(self, outs, *a, **k)

    def spy_snapshot(carry):
        log.append(("snapshot", count("snapshot")))
        return snapshot(carry)

    monkeypatch.setattr(evaluator, "begin", spy_begin)
    monkeypatch.setattr(SegmentEngine, "dispatch_segment", spy_dispatch)
    monkeypatch.setattr(SegmentEngine, "drain", spy_drain)
    monkeypatch.setattr(runner, "_snapshot", spy_snapshot)
    res = run_experiment(algo, CFG, tiny_ds, k=2, degree=2, local_steps=2,
                         batch_size=4, lr=0.05, seed=0, cache=cache, **kw)
    assert cache.evaluator_builds == 1       # the spied evaluator ran

    at = {e: i for i, e in enumerate(log)}
    dispatched = [t for kind, t in log if kind == "dispatch"]
    drained = [t for kind, t in log if kind == "drain"]
    assert dispatched == list(range(len(dispatched)))
    assert drained == list(range(len(drained)))
    if "target_acc" in kw:
        assert res.comm.rounds[-1] == plan[0].start + plan[0].length
        assert (dispatched, drained) == ([0, 1], [0])
    else:
        assert len(dispatched) == len(drained) == len(plan)
    assert count("snapshot") == (len(dispatched) - 1 if ckpt else 0)
    for t in dispatched[:-1]:
        nxt = at[("dispatch", t + 1)]
        if t in evals:
            assert at[("eval", t)] < nxt
        if ckpt:
            assert at[("snapshot", t)] < nxt
        assert nxt < at[("drain", t)]


def test_engine_loop_holds_no_stale_state(tiny_ds, monkeypatch):
    """The loop keeps no reference to a carry it has replaced: the device
    bytes alive at every drain stay level, the last segment's (after
    ``finalize`` made a new state) included. Holding the un-finalized
    carry there costs one extra copy of the state over the last eval:
    2.9% of ``peak_hbm_gb`` in ``lenet-facade-32`` on a TPU v5e."""
    live = []
    drain = SegmentEngine.drain

    def spy_drain(self, outs, *a, **k):
        live.append(sum(x.nbytes for x in jax.live_arrays()
                        if not x.is_deleted()))
        return drain(self, outs, *a, **k)

    monkeypatch.setattr(SegmentEngine, "drain", spy_drain)
    run_experiment("facade", CFG, tiny_ds, rounds=6, eval_every=2, k=2,
                   degree=2, local_steps=2, batch_size=4, lr=0.05, seed=0)
    state = algo_setup("facade", make_binding(CFG), jax.random.PRNGKey(0),
                       tiny_ds.n_nodes, 2, degree=2, local_steps=2,
                       lr=0.05).state
    one_state = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    assert len(live) == 3
    assert max(live) - live[0] < one_state // 2


# ------------------------------------------------------ segment planning --
def test_segment_plan_cuts_at_evals_and_warmup():
    plan = segment_plan(10, 4, warmup_rounds=3)
    assert plan == [Segment(0, 3, True, False),    # warmup cut, no eval
                    Segment(3, 1, False, True),    # eval at round 4
                    Segment(4, 4, False, True),    # eval at round 8
                    Segment(8, 2, False, True)]    # final partial + eval
    # no warmup: spans are exactly the eval strides
    assert segment_plan(8, 4) == [Segment(0, 4, False, True),
                                  Segment(4, 4, False, True)]
    # warmup covering everything: every segment is warmup
    assert all(s.warmup for s in segment_plan(4, 2, warmup_rounds=9))
    assert segment_plan(0, 4) == []


# ------------------------------------------------------------ record_bulk --
def test_record_bulk_matches_per_round_records():
    a, b = CommLog(), CommLog()
    rb = np.asarray([100.0, 250.0, 50.0], np.float32)
    rs = np.asarray([1.0, 2.0, 0.5], np.float32)
    for i in range(3):
        a.record(i + 1, float(rb[i]), round_s=float(rs[i]))
    b.record_bulk(np.arange(1, 4), rb, rs)
    assert a.rounds == b.rounds
    assert a.bytes == b.bytes
    assert a.seconds == b.seconds
    assert a.acc == b.acc and a.evaled == b.evaled


def test_record_bulk_backfills_and_never_crosses_target():
    log = CommLog()
    log.record(1, 100, acc=0.4, round_s=1.0)
    log.record_bulk(np.arange(2, 5), np.full(3, 100.0), np.full(3, 1.0))
    assert log.acc[-1] == 0.4 and log.evaled[-1] is False
    assert log.bytes_to_target(0.3) == 100      # only the measured round
    assert log.bytes_to_target(0.4) == 100
    assert log.bytes_to_target(0.5) is None
    log.record(5, 100, acc=0.9, round_s=1.0)
    assert log.bytes_to_target(0.5) == 500
    assert log.seconds == [1.0, 2.0, 3.0, 4.0, 5.0]
    # empty bulk append is a no-op
    log.record_bulk(np.asarray([]), np.asarray([]), np.asarray([]))
    assert len(log.rounds) == 5
    with pytest.raises(ValueError):
        log.record_bulk(np.arange(2), np.zeros(3), np.zeros(3))


# ------------------------------------------------------- padded evaluator --
def test_padded_eval_batches_shape_stable():
    x = np.arange(10 * 3, dtype=np.float32).reshape(10, 3)
    xb, mask = pipeline.padded_eval_batches(x, 4)
    assert xb.shape == (3, 4, 3) and mask.shape == (3, 4)
    assert mask.sum() == 10
    np.testing.assert_array_equal(xb.reshape(-1, 3)[mask.reshape(-1) > 0], x)
    # exact multiple: no padding
    xb2, mask2 = pipeline.padded_eval_batches(x[:8], 4)
    assert xb2.shape == (2, 4, 3) and mask2.sum() == 8
    # the old ragged-slice generator is gone: padded is the only eval API
    assert not hasattr(pipeline, "eval_batches")


def test_vectorized_evaluator_matches_per_node_loop(tiny_ds):
    """The one-jit-per-cluster evaluator reproduces the legacy per-node,
    ragged-batch evaluation exactly (same preds, same accuracy)."""
    binding = make_binding(CFG)
    setup = algo_setup("el", binding, jax.random.PRNGKey(0),
                       tiny_ds.n_nodes, 2, degree=2, local_steps=2, lr=0.05)
    models = setup.models_of(setup.state)
    evaluate = make_evaluator(binding, tiny_ds.node_cluster,
                              tiny_ds.test_x, tiny_ds.test_y, batch=5)
    accs, preds_c, labels_c, node_acc = evaluate(models)

    from repro.models import cnn as cnn_mod
    node_cluster = np.asarray(tiny_ds.node_cluster)
    for c, y in enumerate(tiny_ds.test_y):
        nodes = np.where(node_cluster == c)[0]
        per_node = []
        for i in nodes:
            p_i = jax.tree.map(lambda l: l[i], models)
            logits = cnn_mod.forward(CFG, p_i, jnp.asarray(tiny_ds.test_x[c]))
            per_node.append(np.asarray(jnp.argmax(logits, -1)))
        ref_acc = float(np.mean([(p == np.asarray(y)).mean()
                                 for p in per_node]))
        assert accs[c] == pytest.approx(ref_acc, abs=1e-12)
        np.testing.assert_array_equal(preds_c[c], per_node[0])
        np.testing.assert_array_equal(labels_c[c], np.asarray(y))
        # the per-node accuracy vector (per-tier fairness tables) agrees
        # with the per-node reference loop, at the node's global index
        for i, p in zip(nodes, per_node):
            assert node_acc[i] == pytest.approx(
                float((p == np.asarray(y)).mean()), abs=1e-12)
