"""The token rate is counted between whole reports of the program's counter,
so it does not depend on where the window's edges fall between two reports."""

import threading
import time

from benchmark.run import CounterWatch


class FakeMetrics:
    """A counter that moves by 100 every ``period_s``, as the batcher's does
    once per chunk."""

    def __init__(self, period_s: float):
        self.t0, self.period_s = time.perf_counter(), period_s

    def counter_state(self):
        n = int((time.perf_counter() - self.t0) / self.period_s)
        return {"scheduler.tokens_generated": 100.0 * n}, {}


def _watch(period_s: float, window_s: float) -> CounterWatch:
    w = CounterWatch(FakeMetrics(period_s), "scheduler.tokens_generated", every_s=0.001)
    w.start()
    threading.Event().wait(window_s)
    w.stop.set()
    w.join()
    return w


def test_rate_over_whole_reports_does_not_follow_the_windows_edges():
    true_rate = 100.0 / 0.05
    for window_s in (0.52, 0.545, 0.58):  # 10.4, 10.9 and 11.6 reports: a staircase for tokens / window
        w = _watch(0.05, window_s)
        assert len(w.marks) in (10, 11) and abs(w.rate() / true_rate - 1) < 0.03
    assert _watch(0.4, 0.5).rate() is None  # one report: nothing to take a rate between
