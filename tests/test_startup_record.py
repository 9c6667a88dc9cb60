"""The program's own start-up clock (``deepvision_tpu/startup.py``,
``obs/trace.py``): the per-program compile record, the start-up spans,
the ``[startup]`` ready line and the ``compiles_after_ready`` counter."""

import glob
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from deepvision_tpu import startup
from deepvision_tpu.obs import trace
from deepvision_tpu.obs.metrics import default_registry
from deepvision_tpu.obs.trace import Tracer

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def record(monkeypatch):
    """A record of this test's events alone; the process's own record
    (where an earlier test installed one) is kept from declaring
    anything late."""
    if startup._RECORD is not None:
        monkeypatch.setattr(startup._RECORD, "ready_at", None)
    rec = startup.CompileRecord().install()
    yield rec
    rec.uninstall()


def _program(name: str, scale: float):
    """A function no other test has traced, called ``name``."""
    def fn(x):
        return jnp.sin(x) * scale + 1.0
    fn.__name__ = fn.__qualname__ = name
    return fn


def test_one_function_at_two_shapes_is_two_of_each_phase_under_one_name(
        record):
    x3, x5 = jnp.ones(3), jnp.ones(5)
    f = jax.jit(_program("two_shapes", 3.0))
    f(x3)
    f(x5)
    f(x3)                       # cached: no event
    p = record.programs["jit(two_shapes)"]
    assert (p["traces"], p["lowerings"], p["compiles"]) == (2, 2, 2)
    assert p["trace_s"] > 0 and p["lower_s"] > 0 and p["compile_s"] > 0
    # jnp.sin and the product are traced inside it: no programs of their own
    assert "jit(sin)" not in record.programs
    summary = record.summary()
    assert summary["programs"] >= 1 and summary["complete"]
    assert "jit(two_shapes)" in [name for name, _ in summary["top"]]


_FETCH = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from deepvision_tpu import startup
rec = startup.CompileRecord().install()
def cached(x):
    return jnp.cos(x) * 2.0
x = jnp.ones(4)
jax.jit(cached)(x).block_until_ready()
jax.clear_caches()
jax.jit(cached)(x).block_until_ready()
print(json.dumps(rec.programs["jit(cached)"]))
"""


def test_a_fetch_from_the_persistent_cache_is_a_hit_with_its_seconds(
        tmp_path):
    """Its own process: the persistent cache is set up once a process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", _FETCH, str(tmp_path / "cache")],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    p = json.loads(proc.stdout.strip().splitlines()[-1])
    assert (p["compiles"], p["cache_misses"], p["cache_hits"]) == (2, 1, 1)
    assert p["traces"] == 2      # clear_caches drops the trace too
    assert p["fetch_s"] > 0 and p["compile_s"] > 0


def test_cache_events_land_on_the_program_compiling_on_their_thread():
    """Two compiles open at once on two threads: each thread's hit,
    miss and fetch seconds go to its own program."""
    rec = startup.CompileRecord()       # fed by hand, not by JAX
    both_open = threading.Barrier(2)
    both_told = threading.Barrier(2)

    def compile_on_this_thread(name: str, hit: bool):
        t = time.time()
        rec._on_start(startup.COMPILE_EVENT, t, fun_name=name)
        both_open.wait()
        if hit:
            rec._on_event(startup.HIT_EVENT)
            rec._on_seconds(startup.FETCH_EVENT, 0.25)
        else:
            rec._on_event(startup.MISS_EVENT)
        both_told.wait()
        rec._on_end(startup.COMPILE_EVENT, t, t + 1.0, fun_name=name)

    threads = [threading.Thread(target=compile_on_this_thread, args=a)
               for a in (("jit(fetched)", True), ("jit(built)", False))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    fetched, built = rec.programs["jit(fetched)"], rec.programs["jit(built)"]
    assert (fetched["cache_hits"], fetched["cache_misses"]) == (1, 0)
    assert (built["cache_hits"], built["cache_misses"]) == (0, 1)
    assert fetched["fetch_s"] == 0.25 and built["fetch_s"] == 0.0
    assert fetched["compile_s"] == pytest.approx(0.75)
    assert built["compile_s"] == pytest.approx(1.0)


def test_many_threads_at_once_lose_no_event():
    """More threads than cores, switching often, each opening and closing
    compiles with a hit in them: every count and second arrives."""
    rec = startup.CompileRecord(capacity=100)
    threads_n, each = 3 * (os.cpu_count() or 2), 200
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work(i: int):
        name = f"jit(p{i % 3})"
        for _ in range(each):
            t = time.time()
            rec._on_start(startup.COMPILE_EVENT, t, fun_name=name)
            rec._on_event(startup.HIT_EVENT)
            rec._on_seconds(startup.FETCH_EVENT, 0.5)
            rec._on_end(startup.COMPILE_EVENT, t, t + 1.0, fun_name=name)

    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    total = threads_n * each
    s = rec.summary()
    assert (s["compiles"], s["cache_hits"]) == (total, total)
    assert s["fetch_s"] == pytest.approx(0.5 * total)
    assert s["compile_s"] == pytest.approx(0.5 * total)
    assert len(rec.intervals) == 100 and rec.dropped == total - 100


def test_a_nested_compile_leaves_the_enclosing_trace_s_seconds():
    rec = startup.CompileRecord()
    t = time.time()
    rec._on_start(startup.TRACE_EVENT, t, fun_name="outer")
    rec._on_start(startup.TRACE_EVENT, t, fun_name="inline")
    rec._on_end(startup.TRACE_EVENT, t, t + 0.5, fun_name="inline")
    rec._on_start(startup.COMPILE_EVENT, t + 1, fun_name="jit(eager)")
    rec._on_end(startup.COMPILE_EVENT, t + 1, t + 3, fun_name="jit(eager)")
    rec._on_end(startup.TRACE_EVENT, t, t + 4, fun_name="outer")
    assert set(rec.programs) == {"jit(outer)", "jit(eager)"}
    assert rec.programs["jit(outer)"]["trace_s"] == pytest.approx(2.0)
    assert rec.programs["jit(eager)"]["compile_s"] == pytest.approx(2.0)
    s = rec.summary()
    assert s["trace_s"] + s["compile_s"] == pytest.approx(4.0)


def test_the_cut_keeps_what_ended_by_the_instant(record):
    x = jnp.ones(2)
    t_a = time.perf_counter()
    jax.jit(_program("before_cut", 5.0))(x)
    cut = time.perf_counter()
    jax.jit(_program("after_cut", 6.0))(x)
    before = record.summary(until=cut)
    after = record.summary()
    assert before["complete"] and before["programs"] < after["programs"]
    assert before["compiles"] == after["compiles"] - 1
    ended = {name for _, t1, _, name, *_ in record.intervals if t1 <= cut}
    assert "jit(before_cut)" in ended and "jit(after_cut)" not in ended
    # the intervals are on the perf_counter clock
    spans = [(t0, t1) for t0, t1, _, name, *_ in record.intervals
             if name == "jit(before_cut)"]
    assert spans and all(t_a - 0.05 <= t0 <= t1 <= cut + 0.05
                         for t0, t1 in spans)


def test_an_overflowed_record_says_a_late_cut_is_incomplete():
    rec = startup.CompileRecord(capacity=1)
    t = time.time()
    for i in range(2):
        rec._on_start(startup.LOWER_EVENT, t + i, fun_name="jit(f)")
        rec._on_end(startup.LOWER_EVENT, t + i, t + i + 0.5,
                    fun_name="jit(f)")
    assert rec.dropped == 1 and rec.programs["jit(f)"]["lowerings"] == 2
    first_end = rec.intervals[0][1]
    assert rec.summary(until=first_end - 0.1)["complete"]
    assert not rec.summary(until=first_end + 5)["complete"]
    assert not rec.summary()["complete"]


def test_startup_spans_are_kept_with_the_tracer_off():
    tr = Tracer()
    assert not tr.active
    with tr.startup("engine"):
        time.sleep(0.002)
    with tr.span("step", cat="train"):      # an ordinary span: a no-op
        pass
    ((name, t0, dur),) = tr.startup_spans()
    assert name == "startup/engine" and dur >= 0.002
    assert t0 <= time.perf_counter() - dur
    assert len(tr) == 0
    tr.enable()                             # enable() clears the ring only
    assert len(tr.startup_spans()) == 1


def test_startup_phase_makes_each_call_a_span(monkeypatch):
    tr = Tracer()
    monkeypatch.setattr(trace, "_TRACER", tr)

    @trace.startup_phase("load_model")
    def load(x):
        return x + 1

    assert load(1) == 2 and load.__name__ == "load"
    assert [n for n, _, _ in tr.startup_spans()] == ["startup/load_model"]


def test_a_startup_span_is_a_host_event_of_a_running_profile(tmp_path):
    from jax.profiler import ProfileData

    tr = Tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tr.startup("engine"):
            time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    names = [e.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events]
    assert names.count("startup/engine") == 1
    assert [n for n, _, _ in tr.startup_spans()] == ["startup/engine"]


def test_a_compile_after_ready_is_counted_and_named_once(record, capsys):
    counter = default_registry().counter("compiles_after_ready")
    x2, x3 = jnp.ones(2), jnp.ones(3)
    f = jax.jit(_program("late", 7.0))
    before = counter.value
    assert record.declare_ready(time.perf_counter())
    assert not record.declare_ready(time.perf_counter())
    f(x2)
    f(x3)
    assert counter.value - before == 2
    warned = [line for line in capsys.readouterr().err.splitlines()
              if line.startswith(startup.READY_TAG)]
    assert len(warned) == 1 and "jit(late)" in warned[0]
    assert "compiles_after_ready" in warned[0]


def test_mark_ready_prints_one_line_once(monkeypatch, capsys):
    tr = Tracer()
    monkeypatch.setattr(trace, "_TRACER", tr)
    rec = startup.CompileRecord()
    monkeypatch.setattr(startup, "_RECORD", rec)
    with tr.startup("runtime"):
        time.sleep(0.002)
    startup.mark_ready()
    startup.mark_ready()
    lines = [line for line in capsys.readouterr().err.splitlines()
             if line.startswith(startup.READY_TAG)]
    assert len(lines) == 1 and rec.ready_at is not None
    ready = json.loads(lines[0][len(startup.READY_TAG):])
    assert {"since_start_s", "startup/runtime", "trace_s", "lower_s",
            "compile_s", "fetch_s", "programs",
            "unspanned_s"} <= set(ready)
    assert ready["since_start_s"] >= ready["startup/runtime"] > 0
    assert ready["unspanned_s"] == pytest.approx(
        ready["since_start_s"] - ready["startup/runtime"], abs=2e-3)


def test_the_process_start_is_before_now_and_after_boot():
    start = startup.process_start()
    assert start <= time.perf_counter()
    assert start <= startup._IMPORTED + 1e-3


def test_a_tiny_train_run_prints_one_startup_line(tmp_path):
    proc = subprocess.run(
        [sys.executable, "train.py", "-m", "lenet5", "--platform", "cpu",
         "--synthetic-size", "128", "--batch-size", "32", "--epochs", "1",
         "--steps-per-epoch", "2", "--workdir", str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(
            tmp_path / "cache")))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [line for line in proc.stderr.splitlines()
             if line.startswith(startup.READY_TAG + "{")]
    assert len(lines) == 1, proc.stderr[-3000:]
    ready = json.loads(lines[0][len(startup.READY_TAG):])
    for span in ("startup/runtime", "startup/state", "startup/compile"):
        assert ready[span] > 0, span
    assert ready["programs"] > 0 and ready["compile_s"] > 0
    assert ready["since_start_s"] > ready["startup/compile"]
    tally = startup.tagged_json(proc.stderr, startup.COMPILE_TAG)
    assert {"compile_s", "cache_hits", "cache_misses"} <= set(tally)
    assert "jit(classification_train_step)" in [n for n, _ in tally["top"]]
