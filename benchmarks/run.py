"""Run every paper-table benchmark: ``python -m benchmarks.run [--full]
[--only NAME ...]``.

One module per paper table/figure (DESIGN.md §9). ``--quick`` (default)
scales node counts / rounds to CPU; ``--full`` uses paper-shaped configs.

Every suite runs in this one process, except that ``warm_start`` starts
child processes which each need the accelerator. A chip belongs to one
process at a time, so those suites run FIRST, before any in-process suite
has initialised a JAX backend here (``SPAWNS_ACCELERATOR_CHILDREN``).
"""
from __future__ import annotations

import argparse
import sys
import time

from . import (check_regress, churn_resilience, color_shift, comm_cost,
               dryrun_matrix, fair_accuracy, fairness_dp_eo, fault_tolerance,
               k_sensitivity, kernel_bench, label_skew, obs_overhead,
               percluster_accuracy, round_throughput, scale_curve,
               seed_sweep, settlement, topo_adapt, warm_start,
               warmup_ablation)

SUITES = {
    "percluster_accuracy": percluster_accuracy,   # Fig. 3 / Tab. II
    "fair_accuracy": fair_accuracy,               # Fig. 5 / App. D
    "fairness_dp_eo": fairness_dp_eo,             # Fig. 6
    "comm_cost": comm_cost,                       # Fig. 7
    "k_sensitivity": k_sensitivity,               # Fig. 8
    "settlement": settlement,                     # Fig. 9 / App. F
    "warmup_ablation": warmup_ablation,           # App. F mitigation
    "label_skew": label_skew,                     # App. G
    "color_shift": color_shift,                   # App. H
    "churn_resilience": churn_resilience,         # netsim presets sweep
    "resil": fault_tolerance,                     # faults + robust gossip
    "topo_adapt": topo_adapt,                     # adaptive topology policies
    "round_throughput": round_throughput,         # segment engine rounds/sec
    "seed_sweep": seed_sweep,                     # compile-cache sweep vs naive
    "warm_start": warm_start,                     # persistent XLA cache
    "scale_curve": scale_curve,                   # sharded engine scaling
    "obs_overhead": obs_overhead,                 # in-scan telemetry cost
    "kernel_bench": kernel_bench,                 # kernels (systems)
    "dryrun_matrix": dryrun_matrix,               # §Dry-run / §Roofline
    "check_regress": check_regress,               # trajectory perf gate
    #   LAST: diffs the records this very invocation just appended
}

SPAWNS_ACCELERATOR_CHILDREN = ("warm_start",)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-shaped configs (slow on CPU)")
    ap.add_argument("--only", nargs="+", choices=sorted(SUITES),
                    default=None)
    args = ap.parse_args(argv)

    names = args.only or list(SUITES)
    # stable: child-spawning suites first, the rest in their given order
    names = sorted(names, key=lambda n: n not in SPAWNS_ACCELERATOR_CHILDREN)
    failures = []
    for name in names:
        print(f"\n{'='*72}\n== {name}\n{'='*72}", flush=True)
        t0 = time.time()
        try:
            SUITES[name].run(quick=not args.full)
            print(f"[{name}] done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # keep the suite going; report at the end
            import traceback
            traceback.print_exc()
            failures.append((name, repr(e)))
    if failures:
        print("\nFAILED:", failures)
        return 1
    print(f"\nall {len(names)} benchmark suites completed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
