"""Run checks on every experiment of the window, from the program's
``chip_smoke.py``: accuracies finite and in [0, 1], per-round bytes
finite, and cumulative bytes at every evaluation equal to the evaluation
round times the nominal per-round count (``reference.round_bytes``, from
parameter shapes). Returns what failed, empty when the run passed."""
from __future__ import annotations

import numpy as np


def check_run(res, *, rounds: int, per_round: float) -> list[str]:
    bad = []
    for rnd, accs in res.acc_per_cluster:
        a = np.asarray(accs, np.float64)
        if not (np.isfinite(a).all() and (a >= 0).all() and (a <= 1).all()):
            bad.append(f"accuracy {accs} at round {rnd}")
    for rnd, fair in res.fair_acc:
        if not (np.isfinite(fair) and 0 <= fair <= 1):
            bad.append(f"fair accuracy {fair} at round {rnd}")
    cum = np.asarray(res.comm.bytes, np.float64)
    if not np.isfinite(np.diff(cum, prepend=0.0)).all():
        bad.append("non-finite per-round bytes")
    if not res.comm.rounds or res.comm.rounds[-1] != rounds:
        bad.append(f"ran {res.comm.rounds[-1:] or 0} of {rounds} rounds")
    for i, (rnd, evaled) in enumerate(zip(res.comm.rounds, res.comm.evaled)):
        if evaled and cum[i] != rnd * per_round:
            bad.append(f"{cum[i]} bytes after round {rnd}, expected "
                       f"{rnd} x {per_round}")
    return bad
