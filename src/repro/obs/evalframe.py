"""Per-eval fairness telemetry: the ``EvalFrame`` time series.

FACADE's headline claims are *fairness* claims — DP/EO gaps,
worst-cluster accuracy, cluster settlement — but until this module the
repo recorded ``dp``/``eo``/``node_acc`` only as final scalars computed
once at run end, so fairness *over training* was invisible. An
:class:`EvalFrame` promotes every eval to a full fairness observation:
DP, EO, fair accuracy, per-cluster and worst-cluster accuracy, per-tier
accuracy, and cluster-assignment churn since the previous eval.

Cost model (the eval twin of the ``MetricsFrame`` drain contract): the
frame is pure HOST-side bookkeeping over arrays the evaluator already
drains — ``preds_c``/``labels_c``/``node_acc`` out of
``_History.eval_finish`` — so eval telemetry adds **zero extra
dispatches and zero extra device syncs**. It therefore never touches
the ``EngineSpec`` cache key and is recorded whether or not a device
:class:`~repro.obs.frame.ObsConfig` is attached.

:func:`compute_eval_frame` is the ONE shared recording hook both
drivers call (inside ``_History.eval_finish``, the single eval
bottleneck the engine and legacy loops both route through —
the ``compute_frame`` discipline from the PR 6 contract), which is what
keeps the series engine/legacy bit-identical AND keeps the series'
final entry bit-for-bit equal to ``RunResult.dp``/``RunResult.eo``:
the run's final scalars are read OFF the last frame, never recomputed.

The series surfaces four ways: ``RunResult.eval_frames`` (always, so
``repro.sweep.aggregate_cell`` can build per-cell mean/std DP/EO
trajectories), ``Obs.eval_table()`` + ``type:"eval"`` JSONL records
(when an ``Obs`` is attached), the checkpoint history snapshot (resume
preserves the trajectory bit-for-bit, extending the PR 7 guarantee),
and ``repro.obs.report`` (rendered fairness-trajectory tables).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from repro.fairness import (demographic_parity, equalized_odds,
                            fair_accuracy)


class EvalFrame(NamedTuple):
    """One eval's fairness observation. Plain Python scalars/tuples —
    JSON- and checkpoint-friendly, never device arrays."""
    round: int                  # 1-based eval round
    mean_acc: float             # node-weighted mean accuracy (the
    #                             target_acc stop metric)
    fair_acc: float             # paper Eq. 5 (lambda = 2/3)
    dp: float                   # demographic parity gap at this eval
    eo: float                   # equalized odds gap at this eval
    worst_cluster_acc: float    # min over the clusters that exist
    acc: tuple                  # per-cluster accuracy, ``cluster_ids`` order
    cluster_ids: tuple          # which cluster each ``acc`` entry is
    acc_core: float             # mean per-node accuracy, core-tier nodes
    acc_edge: float             # mean per-node accuracy, edge-tier nodes
    #                             (0 when the run has no edge tier)
    tier_gap: float             # acc_core - acc_edge (0 without tiers)
    cluster_churn: float        # nodes whose cluster assignment changed
    #                             since the PREVIOUS eval (0 at the first
    #                             eval and off-FACADE)


EVAL_FIELDS = EvalFrame._fields

# the scalar subset (everything but the ragged per-cluster vectors) —
# what Obs.eval_table() stacks into aligned numpy columns
EVAL_SCALAR_FIELDS = tuple(f for f in EVAL_FIELDS
                           if f not in ("acc", "cluster_ids"))


def compute_eval_frame(rnd: int, accs, cluster_ids, preds_c, labels_c,
                       node_acc, n_classes: int, *, mean_acc: float,
                       tiers=None, prev_cid=None, cid=None) -> EvalFrame:
    """Build one eval's :class:`EvalFrame` — the shared hook both
    drivers call from ``_History.eval_finish``.

    ``accs``/``cluster_ids``/``preds_c``/``labels_c``/``node_acc`` are
    exactly what ``make_evaluator``'s ``finish`` drained (per non-empty
    cluster accuracies + first-node predictions + per-node accuracy);
    ``mean_acc`` is the node-weighted mean the driver already computed
    (passed through, never recomputed, so the stop condition and the
    frame can't drift apart); ``tiers`` is the static per-node tier
    vector (1.0 = edge, ``repro.obs.tiers_of``) or ``None``;
    ``prev_cid``/``cid`` are the cluster-id vectors at the previous and
    current eval (``None`` off-FACADE / at the first eval).

    DP/EO/fair-accuracy are computed HERE with the same
    ``repro.fairness`` functions the final scalars always used — the
    caller reads its ``RunResult.dp``/``eo``/``fair_acc`` entries back
    off the frame, so the series' last entry is bit-for-bit the final
    scalar by construction (pinned in ``tests/test_obs.py``).
    """
    accs = [float(a) for a in accs]
    frame_acc_core = frame_acc_edge = tier_gap = 0.0
    if node_acc is not None:
        node_acc = np.asarray(node_acc, np.float64)
        if tiers is not None:
            edge = np.asarray(tiers, np.float64) > 0.5
            core_acc = node_acc[~edge]
            edge_acc = node_acc[edge]
        else:
            core_acc, edge_acc = node_acc, node_acc[:0]
        frame_acc_core = float(core_acc.mean()) if core_acc.size else 0.0
        frame_acc_edge = float(edge_acc.mean()) if edge_acc.size else 0.0
        if core_acc.size and edge_acc.size:
            tier_gap = frame_acc_core - frame_acc_edge
    churn = 0.0
    if prev_cid is not None and cid is not None:
        churn = float(np.sum(np.asarray(prev_cid) != np.asarray(cid)))
    return EvalFrame(
        round=int(rnd),
        mean_acc=float(mean_acc),
        fair_acc=float(fair_accuracy(accs)),
        dp=float(demographic_parity(preds_c, n_classes)),
        eo=float(equalized_odds(preds_c, labels_c, n_classes)),
        worst_cluster_acc=float(min(accs)) if accs else 0.0,
        acc=tuple(accs),
        cluster_ids=tuple(int(c) for c in cluster_ids),
        acc_core=frame_acc_core, acc_edge=frame_acc_edge,
        tier_gap=tier_gap, cluster_churn=churn)


def eval_table(frames) -> dict:
    """Stack a list of :class:`EvalFrame` into aligned columns:
    numpy arrays for every scalar field (``round`` int64, the rest
    float64) plus ``acc``/``cluster_ids`` as lists-of-tuples (ragged
    across runs with different cluster counts)."""
    out = {}
    for name in EVAL_SCALAR_FIELDS:
        dtype = np.int64 if name == "round" else np.float64
        out[name] = np.asarray([getattr(f, name) for f in frames], dtype)
    out["acc"] = [f.acc for f in frames]
    out["cluster_ids"] = [f.cluster_ids for f in frames]
    return out


def frame_record(frame: EvalFrame) -> dict:
    """The ``type:"eval"`` JSONL record for one frame."""
    rec = {"type": "eval"}
    for name, v in zip(EVAL_FIELDS, frame):
        rec[name] = list(v) if isinstance(v, tuple) else v
    return rec
