"""The trace arithmetic the per-layer readers share, on made-up events."""
import pytest

from chipbench import tracing


def _trace(ops, modules, host):
    return {"devices": {"/device:TPU:0": {tracing.OPS: ops,
                                          tracing.MODULES: modules}},
            "host": {"main": host}}


def _ctx(trace, lo, hi, rounds=2, evals=1, chips=1):
    return tracing.Context(trace, lo, hi, rounds=rounds, evals=evals,
                           nodes=4, chips=chips, flops_per_round=1e9,
                           peak_flops=1e12)


def test_union_merges_overlaps_and_clips():
    iv = [(0, 10), (5, 15), (20, 30), (28, 40), (50, 60)]
    assert tracing.union(iv, 0, 100) == 15 + 20 + 10
    assert tracing.union(iv, 8, 25) == 7 + 5
    assert tracing.union([], 0, 10) == 0


def test_gaps_are_the_complement_of_busy():
    iv = [(10, 20), (15, 30), (40, 50)]
    assert tracing.gaps(iv, 0, 60) == [(0, 10), (30, 40), (50, 60)]
    assert tracing.gaps(iv, 12, 45) == [(30, 40)]


def test_module_seconds_and_busy():
    ops = [["fusion.1", 100, 50], ["convolution.2", 160, 40],
           ["fusion.1", 400, 100]]
    mods = [["jit_segment(7)", 100, 100], ["jit_predict(3)", 400, 100]]
    trace = _trace(ops, mods, [["chipbench.experiment", 50, 550]])
    ctx = _ctx(trace, *tracing.host_spans(trace, "chipbench.experiment")[0])
    assert (ctx.lo, ctx.hi) == (50, 600)
    assert tracing.module_seconds(ctx, "jit_segment") == 100e-9
    assert tracing.module_seconds(ctx, "jit_predict") == 100e-9
    assert tracing.busy(ctx) == [50 + 40 + 100]


def test_a_stale_program_name_is_an_error_not_a_silence():
    mods = [["jit_segment(7)", 100, 100]]
    trace = _trace([["fusion.1", 100, 50]], mods, [])
    with pytest.raises(LookupError, match="jit_segment"):
        tracing.module_seconds(_ctx(trace, 0, 1000), "jit_renamed")
    # no program at all in the window: nothing to read
    assert tracing.module_seconds(_ctx(trace, 500, 1000), "jit_renamed") \
        is None


def test_events_outside_the_window_are_left_out():
    ops = [["fusion", 0, 10], ["fusion", 100, 10]]
    mods = [["jit_segment(1)", 0, 10], ["jit_segment(1)", 100, 10]]
    ctx = _ctx(_trace(ops, mods, []), 50, 200)
    assert tracing.module_seconds(ctx, "jit_segment") == 10e-9
    assert tracing.busy(ctx) == [10]
