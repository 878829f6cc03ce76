"""The fairness observatory, end to end: run FACADE with full telemetry,
read the per-eval DP/EO trajectory, check the run-health verdict, and
render the markdown run report.

    PYTHONPATH=src python examples/obs_demo.py

Everything here is pure observation — the run's trajectory is
bit-for-bit what it would have been with ``obs=None`` — and eval-side
fairness telemetry costs ZERO extra device dispatches: the ``EvalFrame``
series is host bookkeeping over arrays the evaluator drains anyway.
"""
import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "src"))

from repro.configs.facade_paper import lenet
from repro.core.cache import use_compile_cache
from repro.core.runner import run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.obs import Obs, ObsConfig
from repro.obs.report import build_report


def main():
    use_compile_cache()   # compiles persist across runs (repro.core.cache)
    # --- a small imbalanced clustered dataset (quickstart's setup) --------
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=16,
                     test_per_class=32, seed=3)
    ds = make_clustered_data(spec, cluster_sizes=(6, 2),
                             transforms=("rot0", "rot180"))
    cfg = lenet(smoke=True).replace(n_classes=4)

    out_dir = pathlib.Path(tempfile.mkdtemp(prefix="obs-demo-"))
    obs = Obs(ObsConfig(), jsonl=out_dir / "trace.jsonl", out_dir=out_dir)

    # --- one FACADE run with the full observatory attached ----------------
    res = run_experiment("facade", cfg, ds, rounds=24, k=2, degree=2,
                         local_steps=4, batch_size=8, lr=0.05,
                         eval_every=4, warmup_rounds=4, seed=0, obs=obs)

    # --- layer 1: the per-eval fairness trajectory ------------------------
    table = obs.eval_table()
    print("\nper-eval fairness trajectory (DP gap over training):")
    for rnd, dp, eo, worst, churn in zip(
            table["round"], table["dp"], table["eo"],
            table["worst_cluster_acc"], table["cluster_churn"]):
        print(f"  round {rnd:3d}: dp={dp:.3f} eo={eo:.3f} "
              f"worst_cluster={worst:.3f} churn={churn:.0f}")
    last = res.eval_frames[-1]
    assert last.dp == res.dp and last.eo == res.eo   # final scalars ARE
    #                                                  the series' last entry

    # --- layer 2: the run-health verdict ----------------------------------
    manifest = obs.manifests[-1]
    print(f"\nhealth verdict: {manifest.health['verdict']}")
    for issue in manifest.health["issues"]:
        print(f"  {issue['rule']} [{issue['severity']}] rounds "
              f"{issue['round_start']}-{issue['round_end']}: "
              f"{issue['detail']}")
    if not manifest.health["issues"]:
        print("  no issues — a clean run")

    # --- layer 3: the rendered report -------------------------------------
    manifest_path = out_dir / f"manifest_{manifest.name}.json"
    _, markdown = build_report(manifest_path)
    print(f"\nrendered report ({manifest_path}):\n")
    print(markdown)
    print("re-render any time with:\n"
          f"  PYTHONPATH=src python -m repro.obs.report {manifest_path}")


if __name__ == "__main__":
    main()
