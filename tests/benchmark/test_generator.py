"""The open-loop generator: schedule from the seed, latency from the due
time, lateness reported."""

import gc
import queue
import threading
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


class _Telemetry:
    class telemetry:
        rows = batches = 0

        class device_time:
            total_s = 0.0

        class queue_wait:
            total_s = 0.0
            count = 0


ANSWER = {"boxes": [], "scores": [], "classes": []}


class _SlowEngine(_Telemetry):
    """Answers each request a fixed time after a stalled start."""

    def __init__(self, stall_s):
        self.stall_s = stall_s

    def submit(self, x):
        # a blocked first submit: the generator falls behind its schedule
        time.sleep(self.stall_s)
        self.stall_s = 0.0
        fut = Future()
        fut.set_result(ANSWER)
        return fut


def test_latency_runs_from_the_due_time_and_lateness_is_reported():
    images = np.zeros((2, 4, 4, 3), np.float32)
    w = drv.window(_SlowEngine(0.2), images, rate=100.0, seconds=0.1, seed=7,
                   checked=4)
    assert len(w["due"]) == 10 and w["answered"].all()
    # the first request was due at 0 and answered after the stall; the
    # later ones were sent late, and their wait counts as latency
    assert w["latency"][0] >= 0.19
    assert w["latency"][-1] >= 0.2 - w["due"][-1] - 0.01
    assert w["late"][1:].min() >= 0.09 and w["late"][0] < 0.05


class _QueueEngine(_Telemetry):
    """A bounded queue before one worker that answers in order,
    ``service_s`` a request; full, it refuses with a ``retry_after_s``
    as the program's engine does. The first ``submit`` blocks for
    ``stall_s`` (the generator stalled), and the ``fail`` -th submits
    resolve with an exception."""

    def __init__(self, max_queue, service_s=0.002, stall_s=0.0, fail=()):
        from deepvision_tpu.serve.engine import ShedError

        self.shed_error = ShedError
        self.max_queue, self.service_s = max_queue, service_s
        self.stall_s, self.fail = stall_s, set(fail)
        self.pending = self.admitted = 0
        self._lock = threading.Lock()
        self._closed = threading.Event()
        self._q: queue.Queue = queue.Queue()
        self._worker = threading.Thread(target=self._work, daemon=True)
        self._worker.start()

    def _work(self):
        while (item := self._q.get()) is not None:
            k, fut = item
            self._closed.wait(self.service_s)
            with self._lock:
                self.pending -= 1
            if k in self.fail:
                fut.set_exception(RuntimeError("planted"))
            else:
                fut.set_result(dict(ANSWER, k=k))

    def submit(self, x):
        time.sleep(self.stall_s)
        self.stall_s = 0.0
        with self._lock:
            if self.pending >= self.max_queue:
                raise self.shed_error("queue full", 0.05)
            self.pending += 1
            k, self.admitted = self.admitted, self.admitted + 1
        fut = Future()
        self._q.put((k, fut))
        return fut

    def close(self):
        self._closed.set()
        self._q.put(None)
        self._worker.join(timeout=5)
        assert not self._worker.is_alive()


IMAGES = np.zeros((2, 4, 4, 3), np.float32)


def test_a_refused_request_is_offered_again_in_order_and_reads_as_late():
    engine = _QueueEngine(max_queue=8, service_s=0.005, stall_s=0.3)
    w = drv.window(engine, IMAGES, rate=100.0, seconds=0.6, seed=11,
                   checked=4)
    engine.close()
    # 30 requests fell due behind the blocked submit, the queue holds 8:
    # the rest were refused, held, and admitted on a later offer
    assert len(w["due"]) == 60 and w["answered"].all()
    assert w["refused"] == 0
    notes = drv.client_notes(w)
    assert notes["offered_again"] >= 10
    assert notes["offers_refused"] >= notes["offered_again"]
    held = w["refusals"] > 0
    assert np.all(w["held"][held] > 0) and not w["held"][~held].any()
    assert notes["held_ms_max"] == w["held"].max() * 1e3 < 1e3
    # order is kept: completions follow the schedule
    assert np.all(np.diff(w["due"] + w["latency"]) > 0)
    # a held request's latency runs from its due time: lateness, the
    # hold, the queue and the service are all in it
    assert np.all(w["latency"][held] >= (w["late"] + w["held"])[held])
    # the generator's lateness is the stall's and holds no waiting, a
    # held request's own or that of the ones behind it
    assert w["held"].sum() > 0.05
    stall_left = np.maximum(0.0, 0.3 - w["due"])
    assert np.all(w["late"][1:] <= stall_left[1:] + 0.15), w["late"]
    assert w["late"][1] >= 0.25


def test_a_client_refused_for_ever_ends_at_the_close_plus_the_grace():
    engine = _QueueEngine(max_queue=3, service_s=30.0)   # 3 in, then full
    t = time.perf_counter()
    w = drv.window(engine, IMAGES, rate=100.0, seconds=0.2, seed=12,
                   checked=4, grace_s=0.3)
    took = time.perf_counter() - t
    assert 0.5 <= took < 2.0, took
    # three admitted and never answered, the rest never admitted: all
    # twenty are due and unanswered, which is what ``failed`` counts
    assert len(w["due"]) == 20 and not w["answered"].any()
    assert w["refused"] == 17 and w["refusals"][3] >= 2
    assert not w["refusals"][4:].any()      # never offered: held behind it
    assert w["sample"] == [] and w["results"] == {}
    assert np.all(w["latency"] >= 0.5 - w["due"] - 0.01)
    engine.close()


def test_only_the_answers_that_may_be_checked_are_kept():
    order = drv.sample_order(100, 13)
    assert sorted(order.tolist()) == list(range(100))
    assert np.array_equal(order, drv.sample_order(100, 13))
    assert not np.array_equal(order, drv.sample_order(100, 14))
    # the first two of the seed's order fail: the sample moves on
    engine = _QueueEngine(max_queue=64, service_s=0.0, fail=order[:2])
    w = drv.window(engine, IMAGES, rate=200.0, seconds=0.5, seed=13,
                   checked=4)
    engine.close()
    assert w["answered"].sum() == 98
    kept = order[:drv.KEPT_PER_CHECKED * 4].tolist()
    assert set(w["results"]) == set(kept[2:])
    assert all(w["results"][i]["k"] == i for i in kept[2:])
    assert w["sample"] == sorted(kept[2:6])
    again = _QueueEngine(max_queue=64, service_s=0.0)
    w2 = drv.window(again, IMAGES, rate=200.0, seconds=0.5, seed=13,
                    checked=4)
    again.close()
    assert w2["sample"] == sorted(kept[:4]) and len(w2["results"]) == 16


class _CollectingEngine(_Telemetry):
    """The sixth submit stalls and then collects every generation."""

    def __init__(self):
        self.n = 0

    def submit(self, x):
        self.n += 1
        if self.n == 6:
            time.sleep(0.1)
            gc.collect()
        fut = Future()
        fut.set_result(ANSWER)
        return fut


def test_the_collectors_clock_runs_for_the_window_only():
    before = list(gc.callbacks)
    w = drv.window(_CollectingEngine(), IMAGES, rate=100.0, seconds=0.3,
                   seed=14, checked=4)
    assert gc.callbacks == before
    notes = drv.client_notes(w)
    assert notes["gc_gen2_count"] >= 1
    assert notes["gc_ms_max"] >= notes["gc_gen2_ms_max"] > 0
    # the collection fell into the generator's largest lateness
    assert int(np.argmax(w["late"])) in (6, 7) and notes["stall_in_gc"]
    quiet = drv.window(_SlowEngine(0.0), IMAGES, rate=100.0, seconds=0.05,
                       seed=14, checked=4)
    assert quiet["gc_gen2_count"] == 0 and not quiet["stall_in_gc"]
