"""The correctness check against faults in the timed path, and against the
lower-precision control, at a size a CPU test holds.

Each faulty case drives the rest of a run (``run.run_cell``: set-up, the
window, the check; only the look for a chip is skipped) with the program
broken underneath, and sees ``correct`` come out false. The sound case
sees it come out true, under the same limits: those of
``resnet8-facade-32``, whose layers the test cell runs at 16x16 pixels.
"""
import time

import jax
import pytest

from chipbench import correct, run, spec
from chipbench.control import stand_in_reading

TESTS = spec.HERE / "tests" / "data"
CELL = "resnet8-facade-32"


def _cell():
    cell = spec.workload("tiny", TESTS / "workloads", TESTS / "configs")
    real = spec.workload(CELL)
    cell.update(limits=real["limits"], select_margin=real["select_margin"])
    return cell


def _seed():
    """A seed whose checked experiment is the window's first."""
    return next(s for s in range(100) if run.Seeds(s).checked == 0)


def _frozen(monkeypatch):
    """A local step that returns its state unchanged."""
    import repro.core.facade as facade
    monkeypatch.setattr(facade, "local_sgd",
                        lambda binding, params, batches, lr: params)


def _half_batch(monkeypatch):
    """Half of each batch left out, the mean taken over the rest."""
    import repro.models.cnn as cnn
    orig = cnn.loss_fn

    def half(cfg, params, batch):
        b = batch["y"].shape[0] // 2
        return orig(cfg, params, {"x": batch["x"][:b], "y": batch["y"][:b]})

    monkeypatch.setattr(cnn, "loss_fn", half)


def _inverted(monkeypatch):
    """Head selection altered where it is made: each node takes the head
    of greatest loss."""
    import repro.core.facade as facade
    orig = facade._select_heads
    monkeypatch.setattr(facade, "_select_heads",
                        lambda *a, **kw: -orig(*a, **kw))


def _bad_answer(monkeypatch):
    """Every prediction of the evaluator altered where it is made."""
    import repro.core.runner as runner
    orig = runner.make_evaluator

    def make(binding, *a, **kw):
        ev = orig(binding, *a, **kw)
        n_cls = binding.cfg.n_classes
        begin = ev.begin

        def bad_begin(models):
            return [(p + 1) % n_cls for p in begin(models)]

        ev.begin = bad_begin
        return ev

    monkeypatch.setattr(runner, "make_evaluator", make)


def _drive(cell):
    out, checks = run.run_cell(cell, _seed(), 0.1, False, jax.devices()[:1],
                               spec.load_json(spec.BENCHMARK),
                               t0=time.perf_counter())
    return out, checks


def test_sound_run_is_correct():
    out, checks = _drive(_cell())
    assert out["correct"], checks
    assert out["failed"] == 0


@pytest.mark.parametrize("fault", [_frozen, _half_batch, _inverted,
                                   _bad_answer],
                         ids=["frozen", "half_batch", "inverted",
                              "bad_answer"])
def test_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out, checks = _drive(_cell())
    assert not out["correct"], checks


def test_bfloat16_control_is_not_correct():
    cell = _cell()
    reading = stand_in_reading(cell, _seed(), dtype="bfloat16")
    ok, checks = correct.verdict(reading, cell["limits"])
    assert not ok, checks
