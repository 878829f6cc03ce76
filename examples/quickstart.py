"""Quickstart: train FACADE on a small clustered dataset and watch the
minority cluster get fair treatment.

    PYTHONPATH=src python examples/quickstart.py

Reproduces the paper's headline result at CPU scale: a 6:2 imbalanced
two-cluster network (images of the minority cluster rotated 180 deg) where
standard Epidemic Learning under-serves the minority, and FACADE closes
the gap — at the same per-round communication cost.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "src"))

from repro.configs.facade_paper import lenet
from repro.core.cache import use_compile_cache
from repro.core.runner import run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data


def main():
    use_compile_cache()   # compiles persist across runs (repro.core.cache)
    # --- a clustered dataset with feature skew (paper Sec. V-A) -----------
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=16,
                     test_per_class=32, seed=3)
    ds = make_clustered_data(spec, cluster_sizes=(6, 2),
                             transforms=("rot0", "rot180"))
    cfg = lenet(smoke=True).replace(n_classes=4)

    print("nodes:", ds.n_nodes, " clusters:", ds.k,
          " node->cluster:", ds.node_cluster.tolist())

    # --- FACADE vs Epidemic Learning --------------------------------------
    results = {}
    for algo in ("el", "facade"):
        print(f"\n=== {algo.upper()} ===")
        res = run_experiment(algo, cfg, ds, rounds=48, k=2, degree=2,
                             local_steps=4, batch_size=8, lr=0.05,
                             eval_every=12, seed=0, verbose=True)
        results[algo] = res

    el, facade = results["el"], results["facade"]
    print("\n================= summary =================")
    print(f"{'':18s}{'majority':>10s}{'minority':>10s}{'fair_acc':>10s}")
    print(f"{'EL':18s}{el.final_acc[0]:10.3f}{el.final_acc[1]:10.3f}"
          f"{el.best_fair_acc():10.3f}")
    print(f"{'FACADE':18s}{facade.final_acc[0]:10.3f}"
          f"{facade.final_acc[1]:10.3f}{facade.best_fair_acc():10.3f}")
    print(f"\nper-round bytes  EL: {el.comm.bytes[0]:.0f}   "
          f"FACADE: {facade.comm.bytes[0]:.0f}  (same cost, Sec. V-E)")
    print(f"final head choice per node: "
          f"{facade.cluster_history[-1][1].tolist()}")


if __name__ == "__main__":
    main()
