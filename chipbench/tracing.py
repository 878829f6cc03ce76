"""The profiler's trace, reduced to plain events, and the arithmetic the
per-layer metrics share.

A trace here is a dict: ``{"devices": {plane: {line: [[name, start_ns,
dur_ns], ...]}}, "host": {line: [[name, start_ns, dur_ns], ...]}}``, read
from the ``.xplane.pb`` that ``jax.profiler`` writes (device planes are
``/device:TPU:<i>``; host threads are lines of ``/host:CPU``). Device and
host events share one clock, so an annotation made on the host bounds
device events. A small trace in this form is kept with the tests.
"""
from __future__ import annotations

import gzip
import json
import pathlib
from typing import NamedTuple

MODULES = "XLA Modules"     # one event per executed program
OPS = "XLA Ops"             # one event per executed operation
HOST_PLANE = "/host:CPU"


def load_xspace(path) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = out["devices"].setdefault(plane.name, {})
        elif plane.name == HOST_PLANE:
            lines = out["host"]
        else:
            continue
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            evs.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events)
    return out


def find_xspace(log_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def dump(trace: dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def clip(trace: dict, lo: int, hi: int) -> dict:
    """The events that overlap [lo, hi)."""
    def keep(lines):
        return {ln: [e for e in evs if e[1] < hi and e[1] + e[2] > lo]
                for ln, evs in lines.items()}

    return {"devices": {p: keep(lines)
                        for p, lines in trace["devices"].items()},
            "host": keep(trace["host"])}


def load(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def host_spans(trace: dict, name: str) -> list[tuple[int, int]]:
    """(start, end) of every host event called ``name``, in time order."""
    return sorted((s, s + d) for evs in trace["host"].values()
                  for n, s, d in evs if n == name)


def device_events(trace: dict, line: str, lo: int, hi: int):
    """Per device plane: the events of ``line`` that start in [lo, hi)."""
    return {plane: [(n, s, s + d) for n, s, d in lines.get(line, [])
                    if lo <= s < hi]
            for plane, lines in sorted(trace["devices"].items())}


def union(intervals, lo: int, hi: int) -> int:
    """Length of the union of (start, end) intervals clipped to [lo, hi)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle stretches of [lo, hi) between the intervals."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


class Context(NamedTuple):
    """What a per-layer metric's reader is handed."""
    trace: dict
    lo: int               # the traced experiment's annotation, ns
    hi: int
    rounds: int           # rounds in the traced experiment
    evals: int            # evaluations in it
    nodes: int
    chips: int
    flops_per_round: float
    peak_flops: float     # per chip, FLOP/s


def busy(ctx: Context) -> list[int]:
    """Per chip: ns inside the window in which an operation ran."""
    per = device_events(ctx.trace, OPS, ctx.lo, ctx.hi)
    return [union([(s, e) for _, s, e in evs], ctx.lo, ctx.hi)
            for evs in per.values() if evs]


def module_seconds(ctx: Context, prefix: str) -> float | None:
    """Mean over the chips of the device seconds of programs whose name
    starts with ``prefix``; ``None`` when no chip ran a program in the
    window. Where chips ran programs but none of that name, the name is
    stale and this raises, naming the programs that did run."""
    evs = device_events(ctx.trace, MODULES, ctx.lo, ctx.hi)
    per = [sum(e - s for n, s, e in ev if n.startswith(prefix))
           for ev in evs.values()]
    per = [p for p in per if p > 0]
    if per:
        return sum(per) / len(per) / 1e9
    seen = sorted({n.split("(")[0] for ev in evs.values() for n, _, _ in ev})
    if seen:
        raise LookupError(f"no device program named {prefix}* in the traced "
                          f"window; programs there: {seen}")
    return None
