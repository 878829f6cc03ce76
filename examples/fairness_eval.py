"""Fairness audit of trained DL models (paper Sec. V-C/V-D).

    PYTHONPATH=src python examples/fairness_eval.py

Trains FACADE and EL briefly on an imbalanced clustered dataset, then
reports the full fairness panel: per-cluster accuracy, fair accuracy
(Eq. 5, sweeping lambda), demographic parity (Eq. 1), equalized odds
(Eq. 2) — the audit a deployment in the paper's hospital scenario would
run before going live.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "src"))

import numpy as np

from repro.configs.facade_paper import lenet
from repro.core.cache import use_compile_cache
from repro.core.runner import run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.fairness.metrics import fair_accuracy


def main():
    use_compile_cache()   # compiles persist across runs (repro.core.cache)
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=16,
                     test_per_class=32, seed=3)
    ds = make_clustered_data(spec, (7, 1), ("rot0", "rot180"))
    cfg = lenet(smoke=True).replace(n_classes=4)

    panel = {}
    for algo in ("el", "facade"):
        res = run_experiment(algo, cfg, ds, rounds=48, k=2, degree=2,
                             local_steps=4, batch_size=8, lr=0.05,
                             eval_every=12, seed=0)
        panel[algo] = res

    print(f"{'metric':34s}{'EL':>10s}{'FACADE':>10s}")
    el, fa = panel["el"], panel["facade"]
    print(f"{'accuracy majority cluster':34s}{el.final_acc[0]:10.3f}"
          f"{fa.final_acc[0]:10.3f}")
    print(f"{'accuracy minority cluster':34s}{el.final_acc[1]:10.3f}"
          f"{fa.final_acc[1]:10.3f}")
    print(f"{'demographic parity (dn)':34s}{el.dp:10.4f}{fa.dp:10.4f}")
    print(f"{'equalized odds (dn)':34s}{el.eo:10.4f}{fa.eo:10.4f}")
    for lam in (0.5, 2 / 3, 0.9):
        fe = fair_accuracy(el.final_acc, lam=lam)
        ff = fair_accuracy(fa.final_acc, lam=lam)
        print(f"fair accuracy (lambda={lam:.2f}){'':11s}{fe:10.3f}"
              f"{ff:10.3f}")

    gap_el = el.final_acc[0] - el.final_acc[1]
    gap_fa = fa.final_acc[0] - fa.final_acc[1]
    print(f"\ncluster accuracy gap: EL {gap_el:+.3f}  FACADE {gap_fa:+.3f}")
    if gap_fa < gap_el:
        print("FACADE reduces the majority/minority gap "
              "(the paper's Fig. 3 finding).")


if __name__ == "__main__":
    main()
