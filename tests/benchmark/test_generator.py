"""The open-loop generator: schedule from the seed, latency from the due
time, lateness reported."""

import time
from concurrent.futures import Future

import numpy as np

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.drivers import serve_open_loop as drv


def test_same_seed_same_schedule():
    a = drv.schedule(200.0, 5.0, 2 ** 31 + 5)
    b = drv.schedule(200.0, 5.0, 2 ** 31 + 5)
    assert np.array_equal(a, b) and len(a) == 1000
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < 5.0


def test_every_seed_has_the_same_gaps_in_another_order():
    a, b = drv.schedule(50.0, 4.0, 1), drv.schedule(50.0, 4.0, 2)
    assert not np.array_equal(a, b)
    assert np.allclose(np.sort(np.diff(a, prepend=0)[1:]),
                       np.sort(np.diff(b, prepend=0)[1:]), atol=0.05)
    assert len(a) == len(b)


def test_gaps_are_exponential_at_the_rate():
    gaps = np.diff(drv.schedule(100.0, 50.0, 3))
    assert abs(gaps.mean() - 0.01) < 2e-4
    assert abs(np.median(gaps) - 0.01 * np.log(2)) < 3e-4


class _SlowEngine:
    """Answers each request a fixed time after a stalled start."""

    class telemetry:
        rows = batches = 0

        class device_time:
            total_s = 0.0

        class queue_wait:
            total_s = 0.0
            count = 0

    def __init__(self, stall_s):
        self.stall_s = stall_s

    def submit(self, x):
        # a blocked first submit: the generator falls behind its schedule
        time.sleep(self.stall_s)
        self.stall_s = 0.0
        fut = Future()
        fut.set_result({"boxes": [], "scores": [], "classes": []})
        return fut


def test_latency_runs_from_the_due_time_and_lateness_is_reported():
    images = np.zeros((2, 4, 4, 3), np.float32)
    w = drv.window(_SlowEngine(0.2), images, rate=100.0, seconds=0.1, seed=7)
    assert len(w["due"]) == 10 and w["answered"].all()
    # the first request was due at 0 and answered after the stall; the
    # later ones were sent late, and their wait counts as latency
    assert w["latency"][0] >= 0.19
    assert w["latency"][-1] >= 0.2 - w["due"][-1] - 0.01
    assert w["late"][1:].min() >= 0.09 and w["late"][0] < 0.05
