"""The stage split and span arithmetic of ``stages.py``, on made-up
events, on a profile of a tiny run on the CPU, and on a cut of a trace
recorded on the chip.

``data/stages_resnet8-facade-32.json.gz`` is the first traced experiment
of ``stages.py --keep`` on ``resnet8-facade-32`` on a TPU v5 lite:
programs, busy intervals, the ``repro.*`` spans with their stats and the
per-stage intervals. ``.expected.json`` beside it holds what that run
reported; every reader the cell lists, and every number ``stages.read``
gives, reads the same from the cut."""
import json

import pytest

from chipbench import spec, stages, tracing

DATA = spec.HERE / "tests" / "data"
SGD = "jit(segment)/while/body/closed_call/vmap(local_sgd)/while/body/mul"


def _ctx(trace, lo, hi, rounds=2):
    return tracing.Context(trace, lo, hi, rounds=rounds, evals=1, nodes=4,
                           chips=1, flops_per_round=1e9, peak_flops=1e12)


def test_a_scope_under_a_transformation_is_itself():
    assert stages.stages_of(SGD) == ("local_sgd",)
    assert stages.stages_of("jit(segment)/transpose(jvp(gossip))/dot") \
        == ("gossip",)
    assert stages.stages_of("jit(segment)/while/body/add") == ()
    assert stages.stages_of("jit(f)/netsim/topology/sort") == \
        ("netsim", "topology")


def test_nested_ops_of_a_stage_count_once():
    # the SGD while loop and the ops inside it, as the chip nests them
    ops = {"/device:TPU:0": [
        ["jit(segment)/while/body/vmap(local_sgd)/while", 100, 400],
        [SGD, 150, 100], [SGD, 300, 100],
        ["jit(segment)/while/body/select_heads/conv", 520, 30],
        ["jit(segment)/while/body/add", 560, 10],
        ["", 580, 5],                        # no path: left out
        [SGD, 2000, 50]]}                    # outside the window
    st = stages.by_stage(ops, 0, 1000)
    assert st["/device:TPU:0"]["local_sgd"] == [[100, 500]]
    assert stages.stage_ms_per_round(st, "local_sgd", 2) == 400 / 2 / 1e6
    assert stages.stage_ms_per_round(st, "select_heads", 1) == 30 / 1e6
    assert set(st["/device:TPU:0"]) == {"local_sgd", "select_heads",
                                        stages.OTHER}


def test_a_stale_scope_name_is_an_error_not_a_silence():
    st = stages.by_stage({"/device:TPU:0": [[SGD, 0, 10]]}, 0, 100)
    with pytest.raises(LookupError, match="local_sgd"):
        stages.stage_ms_per_round(st, "sgd_renamed", 1)
    # no scoped operation at all (a program without scopes): nothing
    assert stages.stage_ms_per_round(
        stages.by_stage({"/device:TPU:0": [["", 0, 10]]}, 0, 100),
        "local_sgd", 1) is None


def test_upload_bytes_and_span_walls_inside_the_window():
    spans = [["repro.upload", 10, 5, {"bytes": 3_000_000}],
             ["repro.upload", 20, 5, {"bytes": 1_000_000}],
             ["repro.upload", 500, 5, {"bytes": 7}],     # outside
             ["repro.eval", 30, 2_000_000, {"round": 20}]]
    assert stages.h2d_mb(spans, 0, 100) == 4.0
    assert stages.h2d_mb(spans, 40, 100) is None
    assert stages.span_ms(spans, 0, 100) == {"repro.eval": 2.0,
                                              "repro.upload": 1e-05}


def test_idle_goes_to_the_innermost_span_open_over_it():
    trace = {"devices": {"/device:TPU:0": {tracing.OPS: [
        ["busy", 0, 100], ["busy", 400, 100]]}}, "host": {}}
    spans = [["repro.run", 0, 1000, {}], ["repro.upload", 100, 200, {}],
             ["repro.setup", 250, 100, {}]]
    got = stages.idle_by_span(_ctx(trace, 0, 1000), spans)
    # idle: [100, 400) and [500, 1000)
    assert got == {"repro.upload": 150 / 1e6, "repro.setup": 100 / 1e6,
                   "repro.run": (50 + 500) / 1e6}


def test_instruction_names_map_to_their_op_names():
    text = ('  %fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop, '
            'calls=%c, metadata={op_name="jit(segment)/while/body/gossip/'
            'dot_general" source_file="x.py" source_line=3}\n'
            '  ROOT %while.9 = (s32[]) while((s32[]) %t), condition=%a, '
            'body=%b, metadata={op_name="jit(segment)/while"}\n'
            '  %copy.1 = f32[2]{0} copy(f32[2]{0} %x)\n')
    assert stages.hlo_op_paths([text]) == {
        "fusion.3": "jit(segment)/while/body/gossip/dot_general",
        "while.9": "jit(segment)/while"}
    # two variants of one program that name an instruction differently
    other = text.replace("gossip/dot_general", "local_sgd/dot_general")
    assert stages.hlo_op_paths([text, other]) == {
        "while.9": "jit(segment)/while"}


def test_an_op_takes_the_names_of_the_program_it_runs_in():
    paths = {"jit_segment": {"fusion.3": "jit(segment)/gossip/dot"},
             "jit_predict": {"fusion.3": "jit(predict)/predict/conv"}}
    modules = [("jit_segment(7)", 100, 100), ("jit_predict(9)", 300, 50)]
    ops = [("%fusion.3 = f32[2]{0} fusion(...)", 120, 10),
           ("fusion.3", 310, 10), ("fusion.3", 250, 10),
           ("%copy.1 = f32[2]{0} copy(...)", 150, 5)]
    assert stages.name_ops(ops, modules, paths) == [
        ["jit(segment)/gossip/dot", 120, 10],
        ["jit(predict)/predict/conv", 310, 10],
        ["", 250, 10],                       # between programs
        ["", 150, 5]]                        # not in the map


def test_a_cpu_profile_carries_the_program_spans_and_their_stats(tmp_path):
    import jax

    from repro.configs.facade_paper import lenet
    from repro.core.runner import run_experiment
    from repro.data.synthetic import SynthSpec, make_clustered_data

    ds = make_clustered_data(
        SynthSpec(n_classes=4, image_size=16, samples_per_class=8,
                  test_per_class=8, seed=3), cluster_sizes=(3, 1),
        transforms=("rot0", "rot180"))
    cfg = lenet(smoke=True).replace(n_classes=4)
    with jax.profiler.trace(str(tmp_path)):
        run_experiment("facade", cfg, ds, rounds=2, k=2, degree=2,
                       local_steps=2, batch_size=4, eval_every=1)
    scoped = stages.load_scoped(tracing.find_xspace(tmp_path), {})
    names = {n for n, _, _, _ in scoped["spans"]}
    assert {"repro.run", "repro.upload", "repro.setup", "repro.compile",
            "repro.drain", "repro.finalize", "repro.eval",
            "repro.record"} <= names
    lo = min(s for _, s, _, _ in scoped["spans"])
    assert stages.h2d_mb(scoped["spans"], lo, lo + 10**15) * 1e6 >= \
        ds.train_x.nbytes + ds.train_y.nbytes


RECORDED = DATA / "stages_resnet8-facade-32.json.gz"


def test_the_recorded_chip_cut_gives_every_number_the_chip_run_gave():
    import importlib

    from chipbench import run

    kept = stages.load(RECORDED)
    want = json.loads(RECORDED.with_name(
        RECORDED.name.replace(".json.gz", ".expected.json")).read_text())
    ctx = run.context(spec.workload(want["workload"]), kept,
                      want["device"]["count"], want["device"]["kind"])
    for name, value in want["metrics"].items():
        got = importlib.import_module(f"chipbench.metrics.{name}").read(ctx)
        assert got == pytest.approx(value, rel=1e-9), name
    got = stages.read(ctx, kept["stages"], kept["spans"])
    for key in ("h2d_mb_per_run", "idle_ms_by_span", "span_ms",
                "stage_ms_per_round", *stages.STAGE_METRICS):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
