"""Perf regression gate over the benchmark trajectory.

``common.write_bench`` appends every ``BENCH_*`` payload to
``results/bench/TRAJECTORY.jsonl``; this suite diffs each benchmark's
LATEST record against its PREVIOUS one under per-metric tolerance gates,
so a perf claim from PRs 2-9 (engine speedup, obs overhead, sharding
linearity, warm start) can't silently rot between runs.

Semantics:

* a benchmark with fewer than two trajectory records is reported as
  ``baseline`` (nothing to diff yet) — the FIRST full benchmark run
  seeds the gate, it never fails it;
* ``direction="higher"`` passes when
  ``new >= prev - rel_tol*|prev| - abs_tol``; ``"lower"`` mirrors it.
  Tolerances are deliberately loose — CPU benchmark timings are noisy
  and the gate is for *regressions*, not run-to-run jitter;
* a gate ``path`` walks nested dicts with ``"*"`` fanning out over all
  values at that level (e.g. ``results.*.base_rounds_per_sec`` checks
  every benchmarked algorithm); a path absent on EITHER side is skipped
  (schema growth is not a regression);
* any failed gate raises ``RuntimeError`` after the full table prints,
  which is how ``-m benchmarks.run`` reports it.

Registered LAST in ``benchmarks/run.py`` so the gate sees the records
the same invocation just wrote. Results go through :func:`common.save`
(NOT ``write_bench`` — the gate must not append itself to the
trajectory it reads).
"""
from __future__ import annotations

import dataclasses

from repro.obs import read_jsonl

from . import common


@dataclasses.dataclass(frozen=True)
class Gate:
    """One metric's tolerance gate. ``path`` is dot-separated into the
    payload, ``"*"`` fans out over a dict level."""
    path: str
    direction: str            # "higher" = bigger is better, "lower" = smaller
    rel_tol: float = 0.25
    abs_tol: float = 0.0

    def __post_init__(self):
        if self.direction not in ("higher", "lower"):
            raise ValueError(f"direction must be higher|lower, "
                             f"got {self.direction!r}")

    def passes(self, prev: float, new: float) -> bool:
        slack = self.rel_tol * abs(prev) + self.abs_tol
        if self.direction == "higher":
            return new >= prev - slack
        return new <= prev + slack


# per-benchmark gates, keyed by the write_bench name
GATES: "dict[str, tuple]" = {
    "obs": (
        Gate("worst_overhead_pct", "lower", rel_tol=0.0, abs_tol=3.0),
        Gate("results.*.base_rounds_per_sec", "higher"),
        Gate("results.*.obs_rounds_per_sec", "higher"),
    ),
    "throughput": (
        Gate("min_speedup", "higher"),
    ),
    "scale": (
        Gate("linear_frac", "higher", rel_tol=0.0, abs_tol=0.15),
    ),
    "warmstart": (
        Gate("speedup_first_dispatch", "higher", rel_tol=0.5),
    ),
}


def _resolve(payload, path: str) -> "list[tuple[str, float]]":
    """All ``(concrete_path, value)`` leaves ``path`` names in
    ``payload`` — one entry per ``"*"`` expansion, empty when the path
    is absent or a leaf is non-numeric."""
    slots = [("", payload)]
    for part in path.split("."):
        nxt = []
        for prefix, node in slots:
            if not isinstance(node, dict):
                continue
            if part == "*":
                nxt += [(f"{prefix}.{k}".lstrip("."), v)
                        for k, v in sorted(node.items())]
            elif part in node:
                nxt.append((f"{prefix}.{part}".lstrip("."), node[part]))
        slots = nxt
    return [(p, float(v)) for p, v in slots
            if isinstance(v, (int, float)) and not isinstance(v, bool)]


def check(records: "list[dict]", gates: "dict[str, tuple]") -> dict:
    """Pure comparison: group trajectory ``records`` (each
    ``{"name":..., "payload":...}``) by benchmark name, diff latest vs
    previous under ``gates``. Returns ``{"rows": [...], "failures":
    [...], "baselines": [names...]}``."""
    by_name: "dict[str, list]" = {}
    for rec in records:
        by_name.setdefault(rec["name"], []).append(rec["payload"])
    rows, failures, baselines = [], [], []
    for name, gs in sorted(gates.items()):
        history = by_name.get(name, [])
        if len(history) < 2:
            baselines.append(name)
            continue
        prev, new = history[-2], history[-1]
        for gate in gs:
            prev_leaves = dict(_resolve(prev, gate.path))
            for cpath, new_v in _resolve(new, gate.path):
                if cpath not in prev_leaves:
                    continue        # schema growth, not a regression
                prev_v = prev_leaves[cpath]
                ok = gate.passes(prev_v, new_v)
                row = {"bench": name, "metric": cpath,
                       "direction": gate.direction,
                       "prev": prev_v, "new": new_v, "ok": ok}
                rows.append(row)
                if not ok:
                    failures.append(row)
    return {"rows": rows, "failures": failures, "baselines": baselines}


def run(quick: bool = True) -> dict:
    records = read_jsonl(common.trajectory_path())
    verdict = check(records, GATES)
    if verdict["rows"]:
        print(common.table(
            ["bench", "metric", "dir", "prev", "new", "ok"],
            [[r["bench"], r["metric"], r["direction"],
              f"{r['prev']:.3f}", f"{r['new']:.3f}",
              "ok" if r["ok"] else "FAIL"] for r in verdict["rows"]]))
    for name in verdict["baselines"]:
        print(f"  [{name}] baseline only "
              "(< 2 trajectory records; nothing to diff)")
    payload = {"n_records": len(records),
               "n_checked": len(verdict["rows"]),
               "n_failed": len(verdict["failures"]),
               "baselines": verdict["baselines"],
               "rows": verdict["rows"]}
    common.save("check_regress", payload)   # save, NOT write_bench: the
    #                                         gate must not feed itself
    if verdict["failures"]:
        raise RuntimeError(
            "benchmark regression gate failed: " + "; ".join(
                f"{f['bench']}.{f['metric']} {f['prev']:.3f} -> "
                f"{f['new']:.3f} ({f['direction']} is better)"
                for f in verdict["failures"]))
    print(f"regression gate: {len(verdict['rows'])} metrics checked, "
          "0 failures")
    return payload


if __name__ == "__main__":
    run()
