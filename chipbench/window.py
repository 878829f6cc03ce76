"""The measured window: whole experiments back to back until the deadline.

The window starts experiments while ``seconds`` have not passed since its
start, and at least ``least`` of them; the experiment running at the
deadline finishes and counts. Its
rate is all the work over all the time: nodes x rounds of every experiment
that completed and passed its checks, over the seconds from the window's
start to the end of the last experiment.
"""
from __future__ import annotations

from typing import Callable, NamedTuple


class Experiment(NamedTuple):
    start: float         # host clock, s
    end: float
    node_rounds: int
    ok: bool             # finished and passed the run checks


def drive(run_one: Callable[[int], tuple[int, bool]], seconds: float,
          clock: Callable[[], float], least: int = 1
          ) -> tuple[float, list[Experiment]]:
    """Call ``run_one(i)`` for i = 0, 1, ... while the window is open.
    ``run_one`` returns (node-rounds, ok). Returns the window's start and
    the experiments in order."""
    t0 = clock()
    done = []
    while clock() - t0 < seconds or len(done) < least:
        a = clock()
        node_rounds, ok = run_one(len(done))
        done.append(Experiment(a, clock(), node_rounds, ok))
    return t0, done


def rate(t0: float, done: list[Experiment]) -> float | None:
    """Node-rounds per second over the window; ``None`` when nothing
    completed."""
    work = sum(e.node_rounds for e in done if e.ok)
    if not done or work == 0:
        return None
    return work / (done[-1].end - t0)
