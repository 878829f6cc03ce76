"""The readers of the program's own ``repro.*`` spans, on made-up
events: what they read, and that a program without the spans (one from
before they existed) gives nothing rather than an error."""
import pytest

from chipbench import tracing
from chipbench.metrics import eval_host_ms_per_eval, start_idle_ms_per_run


def _ctx(ops, modules, host, lo=0, hi=10_000, evals=1):
    trace = {"devices": {"/device:TPU:0": {tracing.OPS: ops,
                                           tracing.MODULES: modules}},
             "host": {"python": host}}
    return tracing.Context(trace, lo, hi, rounds=20, evals=evals, nodes=4,
                           chips=1, flops_per_round=1e9, peak_flops=1e12)


OPS = [["copy", 1_000, 500], ["fusion", 4_000, 3_000]]
MODS = [["jit_init(1)", 1_000, 500], ["jit_segment(2)", 4_000, 3_000],
        ["jit_predict(3)", 8_000, 1_000]]
HOST = [["chipbench.experiment", 0, 10_000], ["repro.run", 200, 9_000],
        ["repro.eval", 7_500, 2_000], ["repro.eval", 9_600, 300]]


def test_start_idle_is_the_idle_before_the_first_segment():
    # from repro.run at 200 to jit_segment at 4000, busy 1000-1500
    assert start_idle_ms_per_run.read(_ctx(OPS, MODS, HOST)) == \
        (3_800 - 500) / 1e6


def test_eval_host_is_the_eval_spans_wall_per_eval():
    assert eval_host_ms_per_eval.read(_ctx(OPS, MODS, HOST, evals=2)) == \
        2_300 / 2 / 1e6


def test_a_program_without_spans_gives_nothing():
    host = [["chipbench.experiment", 0, 10_000], ["$runner.py:1 run", 0, 9]]
    ctx = _ctx(OPS, MODS, host)
    assert start_idle_ms_per_run.read(ctx) is None
    assert eval_host_ms_per_eval.read(ctx) is None


def test_spans_outside_the_window_are_left_out():
    ctx = _ctx(OPS, MODS, HOST, lo=300, hi=10_000)
    assert start_idle_ms_per_run.read(ctx) is None
    assert eval_host_ms_per_eval.read(ctx) == 2_300 / 1e6


def test_a_stale_program_name_is_an_error_not_a_silence():
    mods = [["jit_renamed(2)", 4_000, 3_000]]
    with pytest.raises(LookupError, match="jit_renamed"):
        start_idle_ms_per_run.read(_ctx(OPS, mods, HOST))
