"""The window's arithmetic on a made-up schedule."""
from chipbench import window


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_experiment_running_at_the_deadline_finishes_and_counts():
    clock = Clock()
    lengths = [4.0, 4.0, 4.0, 4.0]

    def run_one(i):
        clock.t += lengths[i]
        return 32 * 120, True

    t0, done = window.drive(run_one, 10.0, clock)
    # starts at 0, 4 and 8 s; the third ends at 12 s, past the deadline
    assert [e.start - t0 for e in done] == [0.0, 4.0, 8.0]
    assert done[-1].end - t0 == 12.0
    assert window.rate(t0, done) == 3 * 32 * 120 / 12.0


def test_failed_experiments_add_no_work_but_their_time_counts():
    clock = Clock()

    def run_one(i):
        clock.t += 5.0
        return 100, i != 1

    t0, done = window.drive(run_one, 10.0, clock)
    assert len(done) == 2
    assert window.rate(t0, done) == 100 / 10.0
    assert sum(not e.ok for e in done) == 1


def test_no_completed_work_gives_no_rate():
    clock = Clock()

    def run_one(i):
        clock.t += 20.0
        return 100, False

    t0, done = window.drive(run_one, 10.0, clock)
    assert window.rate(t0, done) is None


def test_window_holds_at_least_the_least_experiments():
    clock = Clock()

    def run_one(i):
        clock.t += 8.0
        return 10, True

    t0, done = window.drive(run_one, 10.0, clock, least=3)
    assert len(done) == 3
    assert window.rate(t0, done) == 30 / 24.0
