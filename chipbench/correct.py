"""The comparison that decides ``correct``.

One experiment of the window, drawn from the seed, is checked over its
first segment: the ``eval_every`` rounds up to its first evaluation. The
reference (``reference.py``) replays those rounds from the experiment's
seed at float32 ``HIGHEST`` precision, forced onto the heads the run chose,
as a served model's reference is run over the tokens it served. The
numbers compared:

``model_gap``
    training (local SGD and gossip): for each parameter leaf of the node
    models at the evaluation, stacked over the nodes, the gap between the
    norm of the run's change from the initial model and the norm of the
    reference's, over the reference's norm of that leaf's change or of the
    median leaf's, whichever is larger; the worst leaf. Leaves that the
    reference moves by under a thousandth of the median leaf's change are
    left out: they move by rounding alone.
``model_drift``
    the same leaves and scale, but the norm of the difference between the
    run's change and the reference's: where ``model_gap`` sees the size of
    the change, this sees its direction too.
``select_miss``
    FACADE's head selection and cluster ids: the share of the (round,
    node) choices of the segment for which the reference's loss of the
    head the run chose lies above its least loss over the heads by more
    than the cell's ``select_margin``. A near tie may fall either way on
    rounding; a wrong head costs more than the margin.
``select_gap``
    the largest of those excess losses.
``pred_gap``
    the evaluator's answers: the share of the predictions it made at the
    evaluation, over every node and test image of every cluster, that
    differ from the reference's predictions with the same node models.
``acc_gap``
    the per-cluster accuracies the run reported there, against those of
    the reference's predictions: the largest difference.
``bytes_gap``
    the ``CommLog``: bytes after the segment against the reference's count
    from its own parameter shapes; exact.
"""
from __future__ import annotations

import jax
import numpy as np

from . import reference as ref

NUMBERS = ("model_gap", "model_drift", "select_miss", "select_gap",
           "pred_gap", "acc_gap", "bytes_gap")


def _norms(tree, init):
    """Per leaf: the norm of the change of the node-stacked leaf."""
    return [float(np.linalg.norm(np.asarray(l, np.float64)
                                 - np.asarray(i, np.float64)[None]))
            for l, i in zip(jax.tree.leaves(tree), jax.tree.leaves(init))]


def model_gaps(models, want: ref.Result) -> tuple[float, float]:
    """(``model_gap``, ``model_drift``): the worst leaf's of each."""
    got = _norms(models, want.init)
    ref_n = _norms(want.models, want.init)
    diff = [float(np.linalg.norm(np.asarray(g, np.float64)
                                 - np.asarray(w, np.float64)))
            for g, w in zip(jax.tree.leaves(models),
                            jax.tree.leaves(want.models))]
    med = float(np.median(ref_n))
    keep = [i for i, r in enumerate(ref_n) if r >= 1e-3 * med]
    scale = [max(ref_n[i], med) for i in keep]
    return (max(abs(got[i] - ref_n[i]) / d for i, d in zip(keep, scale)),
            max(diff[i] / d for i, d in zip(keep, scale)))


def as_rows(pred, m: int, count: int) -> np.ndarray:
    """The evaluator's [batches, m, B] predictions as [m, count]."""
    p = np.asarray(pred)
    if p.ndim == 3:
        p = np.moveaxis(p, 1, 0).reshape(m, -1)
    return p[:, :count]


def compare(s: ref.Setup, ds, *, seed: int, rounds: int, final: bool,
            cids, models, preds, accs, cum_bytes,
            select_margin: float) -> dict:
    """The numbers for one checked segment of a run (or of a stand-in).

    ``cids`` [rounds, n]: the heads the run chose; ``models``: its node
    models at the evaluation; ``preds``: per cluster, the predictions its
    evaluator made there; ``accs``: the per-cluster accuracies it
    reported; ``cum_bytes``: its ``CommLog`` bytes after the segment.
    Besides the numbers, ``"excess"`` holds the per-choice excess losses
    [rounds, n] that ``select_miss`` counts above ``select_margin``."""
    want = ref.run(s, seed, ds.train_x, ds.train_y, rounds, final=final,
                   forced=np.asarray(cids))
    ref_preds = ref.predictions(s._replace(fault=None), models,
                                ds.node_cluster, ds.test_x)
    differ = total = 0
    acc_gap = 0.0
    for got, mine, y, a in zip(preds, ref_preds, ds.test_y, accs):
        got = as_rows(got, mine.shape[0], mine.shape[1])
        differ += int((got != mine).sum())
        total += mine.size
        acc_gap = max(acc_gap, abs(float(a) - float((mine == y[None]).mean())))
    gap, drift = model_gaps(models, want)
    return {"model_gap": gap, "model_drift": drift,
            "select_miss": float((want.excess > select_margin).mean()),
            "select_gap": float(want.excess.max()),
            "excess": want.excess,
            "pred_gap": differ / total,
            "acc_gap": acc_gap,
            "bytes_gap": abs(float(cum_bytes) - want.bytes)}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit. The numbers the
    cell's file gives a limit are compared; one that is missing or not
    finite fails, and so does a run with nothing compared. The others are
    reported beside the limit ``None``."""
    checks, ok = {}, bool(numbers) and bool(limits)
    for name in NUMBERS:
        v, lim = numbers.get(name), limits.get(name)
        checks[name] = {"value": v, "limit": lim}
        if lim is not None:
            ok &= bool(v is not None and np.isfinite(v) and v <= lim)
    return ok, checks
