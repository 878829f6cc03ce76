"""netsim demo: the same FACADE experiment on an ideal network, on flaky
edge devices, through a scheduled partition-then-heal scenario, and under
the netsim-v2 axes — bursty Gilbert–Elliott links, a heterogeneous
core/edge link fabric, and asynchronous stale gossip.

    PYTHONPATH=src python examples/netsim_demo.py

Shows the netsim pieces composing with an unmodified algorithm: preset
conditions (churn/loss/stragglers), the latency/bandwidth cost model
(CommLog grows a simulated-time axis), seeded event schedules (a
reproducible burst failure + partition), per-link Markov loss state and
staleness buffers carried on device through the scan engine. Note how
"async-edge" trades a little accuracy for traffic AND simulated hours
(stale stragglers send nothing and never gate the round) — the
communication-cost axis the paper's Fig. 7 measures. Swap "facade" for
any of "el" / "dpsgd" / "deprl" / "dac" — the `net=` argument works for
all.

The next section reruns the nastiest preset ("edge-v2") with an
adaptive topology policy (`repro.topo`): per-link goodput EWMAs steer the
degree budget toward links that deliver, with a `min_inclusion` fairness
floor so edge-tier nodes stay in the mixture — and prints the
bytes/simulated-hours delta vs the blind uniform sampler.

The final section adds hostile nodes (`repro.resil`): a quarter of the
fleet publishes NaN-poisoned models every round on top of edge-v2's
bursty, tiered, async links. With the robust gossip guard (the default)
the mixture quarantines the poison and both tiers keep learning; with
`robust=False` one bad sender corrupts every neighbourhood within a
couple of rounds — the per-tier accuracy table shows the gap.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "src"))

from repro.configs.facade_paper import lenet
from repro.core.cache import use_compile_cache
from repro.core.runner import run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.netsim import BurstFailure, NetworkConfig, Partition
from repro.topo import TopoConfig


def main():
    use_compile_cache()   # compiles persist across runs (repro.core.cache)
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=16,
                     test_per_class=32, seed=3)
    ds = make_clustered_data(spec, cluster_sizes=(6, 2),
                             transforms=("rot0", "rot180"))
    cfg = lenet(smoke=True).replace(n_classes=4)

    # a scripted bad day: a third of the fleet dies at round 12 for 6
    # rounds, then the network splits in two camps for rounds 24-32
    bad_day = NetworkConfig.preset(
        "wan", events=(BurstFailure(start=12, duration=6, fraction=0.33),
                       Partition(start=24, duration=8, groups=2)))

    scenarios = {
        "ideal": NetworkConfig.preset("ideal"),
        "edge-churn": NetworkConfig.preset("edge-churn"),
        "wan+events": bad_day,
        # netsim v2: bursty links / core-edge tiers / async stale gossip,
        # then all three at once
        "bursty-wan": NetworkConfig.preset("bursty-wan"),
        "core-edge": NetworkConfig.preset("core-edge"),
        "async-edge": NetworkConfig.preset("async-edge"),
        "edge-v2": NetworkConfig.preset("edge-v2"),
    }

    print(f"{'scenario':<12} {'majority':>9} {'minority':>9} "
          f"{'fair_acc':>9} {'traffic':>10} {'sim time':>9}")
    for name, net in scenarios.items():
        res = run_experiment("facade", cfg, ds, rounds=48, k=2, degree=2,
                             local_steps=4, batch_size=8, lr=0.05,
                             eval_every=12, seed=0, net=net)
        print(f"{name:<12} {res.final_acc[0]:>9.3f} {res.final_acc[1]:>9.3f} "
              f"{res.best_fair_acc():>9.3f} "
              f"{res.comm.bytes[-1]/1e6:>7.1f} MB "
              f"{res.comm.seconds[-1]/3600:>7.2f} h")
        clusters = res.cluster_history[-1][1].tolist()
        print(f"{'':<12} final cluster choice per node: {clusters}")

    # --- adaptive topology (repro.topo) on the nastiest preset: the same
    # --- run with a reliability-driven, fairness-floored sampler instead
    # --- of the blind uniform draw — bytes AND simulated hours drop
    print("\nadaptive vs uniform topology on edge-v2 "
          "(reliability policy, min_inclusion=0.25):")
    kw = dict(rounds=48, k=2, degree=2, local_steps=4, batch_size=8,
              lr=0.05, eval_every=12, seed=0,
              net=NetworkConfig.preset("edge-v2"))
    uni = run_experiment("facade", cfg, ds, **kw)
    ada = run_experiment("facade", cfg, ds,
                         topo=TopoConfig(policy="reliability",
                                         min_inclusion=0.25, decay=0.7),
                         **kw)
    d_bytes = 1.0 - ada.comm.bytes[-1] / uni.comm.bytes[-1]
    d_hours = 1.0 - ada.comm.seconds[-1] / uni.comm.seconds[-1]
    print(f"{'uniform':<12} {uni.comm.bytes[-1]/1e6:7.1f} MB "
          f"{uni.comm.seconds[-1]/3600:7.2f} h "
          f"fair_acc {uni.best_fair_acc():.3f}")
    print(f"{'reliability':<12} {ada.comm.bytes[-1]/1e6:7.1f} MB "
          f"{ada.comm.seconds[-1]/3600:7.2f} h "
          f"fair_acc {ada.best_fair_acc():.3f}")
    print(f"{'':<12} delta: {100*d_bytes:.1f}% fewer bytes, "
          f"{100*d_hours:.1f}% fewer simulated hours")

    # --- hostile nodes (repro.resil) on edge-v2: 25% of senders publish
    # --- NaN-poisoned models each round; the robust gossip guard
    # --- quarantines them, the unguarded mixture collapses
    import dataclasses

    import numpy as np

    from repro.netsim import node_tiers
    from repro.resil import FaultConfig

    print("\nhostile nodes on edge-v2 (25% NaN corruption), robust "
          "guard on vs off:")
    base = NetworkConfig.preset("edge-v2")
    tiers = np.asarray(node_tiers(base, 8))
    print(f"{'guard':<12} {'fair_acc':>9} {'core tier':>10} "
          f"{'edge tier':>10} {'finite':>7}")
    for label, robust in (("robust", True), ("unguarded", False)):
        net = dataclasses.replace(base, faults=FaultConfig(
            corrupt_rate=0.25, corrupt_mode="nan", robust=robust))
        res = run_experiment("facade", cfg, ds, topo=None, net=net, **{
            k: v for k, v in kw.items() if k != "net"})
        acc = np.asarray(res.node_acc, float)
        finite = bool(np.all(np.isfinite(acc)))
        print(f"{label:<12} {res.best_fair_acc():>9.3f} "
              f"{acc[tiers == 0].mean():>10.3f} "
              f"{acc[tiers == 1].mean():>10.3f} "
              f"{'yes' if finite else 'NO':>7}")


if __name__ == "__main__":
    main()
