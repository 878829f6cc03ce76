"""Each per-layer reader on a trace recorded on the chip.

``data/trace_resnet8-facade-32.json.gz`` is the traced experiment of a
``--trace 1`` run of ``resnet8-facade-32`` on a TPU v5 lite, written by
``run.py --keep-trace`` and cut to what the readers read: the programs
(``XLA Modules``) whole, the operations (``XLA Ops``) merged into busy
intervals without their names, and of the host events the annotation and
those over idle gaps. ``.expected.json`` beside it holds what that run
reported. The readers, given the same context, give the same numbers,
and find the programs by the names the chip gave them.
"""
import importlib
import json

import pytest

from chipbench import run, spec, tracing

CELL = "resnet8-facade-32"
DATA = spec.HERE / "tests" / "data"
BENCH = spec.load_json(spec.BENCHMARK)
READERS = [m["name"] for m in spec.per_layer_metrics(CELL, BENCH)]


@pytest.fixture(scope="module")
def recorded():
    trace = tracing.load(DATA / f"trace_{CELL}.json.gz")
    with open(DATA / f"trace_{CELL}.expected.json") as f:
        want = json.load(f)
    ctx = run.context(spec.workload(CELL), trace, want["device"]["count"],
                      want["device"]["kind"])
    return ctx, want


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_what_the_chip_run_reported(name, recorded):
    ctx, want = recorded
    got = importlib.import_module(f"chipbench.metrics.{name}").read(ctx)
    assert got == pytest.approx(want["metrics"][name]["value"], rel=1e-9)


def test_programs_carry_the_names_the_readers_look_for(recorded):
    ctx, _ = recorded
    seen = {n.split("(")[0] for evs in tracing.device_events(
        ctx.trace, tracing.MODULES, ctx.lo, ctx.hi).values()
        for n, _, _ in evs}
    assert {"jit_segment", "jit_predict"} <= seen


def test_shares_lie_between_0_and_100(recorded):
    ctx, want = recorded
    for name in READERS:
        if want["metrics"][name]["unit"] == "%":
            v = want["metrics"][name]["value"]
            assert 0.0 < v < 100.0, name


def test_busy_and_window_seconds(recorded):
    ctx, want = recorded
    busy = tracing.busy(ctx)
    assert sum(busy) / len(busy) / 1e9 == pytest.approx(
        want["device"]["busy_s"], rel=1e-9)
    assert (ctx.hi - ctx.lo) / 1e9 == pytest.approx(
        want["device"]["window_s"], rel=1e-9)
    assert 0 < want["device"]["busy_s"] <= want["device"]["window_s"]


def test_idle_gaps_are_what_the_run_reported(recorded):
    ctx, want = recorded
    bd = run.breakdown(ctx)
    assert bd["idle_gaps"] == want["breakdown"]["idle_gaps"]
    assert 0 < len(want["breakdown"]["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10
