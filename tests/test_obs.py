"""Unified observability (deepvision_tpu/obs/): metric registry
primitives + Prometheus rendering, span tracing + Chrome-trace export +
attribution, profiler/memory hooks, byte-compatibility of the four
refactored telemetry surfaces (serve /stats, feed input_*, recovery_*,
loggers), and the trace_summary / obs_smoke CLI gates."""

from __future__ import annotations

import json
import re
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent.parent))

from deepvision_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    default_registry,
)
from deepvision_tpu.obs.trace import Tracer, summarize_chrome

# one exposition sample: name, optional {labels}, one float
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\"(,[a-zA-Z_][a-zA-Z0-9_]*"
    r"=\"[^\"]*\")*\})?"
    r" [-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|[Ii]nf|[Nn]a[Nn])$")


# ------------------------------------------------------------- registry


def test_registry_get_or_create_and_snapshot():
    reg = Registry()
    c = reg.counter("train_steps")
    c.inc(3)
    assert reg.counter("train_steps") is c  # get-or-create
    reg.gauge("mem_bytes_in_use_dev0").set(1.5e9)
    h = reg.histogram("serve_e2e_latency")
    h.observe(0.010)
    snap = reg.snapshot()
    assert snap["train_steps"] == 3
    assert snap["mem_bytes_in_use_dev0"] == 1.5e9
    assert snap["serve_e2e_latency"]["count"] == 1
    assert snap["serve_e2e_latency"]["mean_ms"] == pytest.approx(10.0)
    # JSON-able end to end (the bench embeds this dict verbatim)
    json.dumps(snap)


def test_registry_type_collision_and_replace_semantics():
    reg = Registry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.register("bad name!", Counter())
    # explicit register replaces: the latest owner wins (a fresh
    # engine's telemetry supersedes a closed one's series)
    old, new = Counter(), Counter()
    reg.register("serve_completed", old)
    reg.register("serve_completed", new)
    new.inc(7)
    assert reg.snapshot()["serve_completed"] == 7


def test_histogram_summary_matches_latencystats_shape():
    h = Histogram()
    for ms in range(1, 101):
        h.observe(ms / 1e3)
    s = h.summary()
    assert s["count"] == 100
    assert 49 <= s["p50_ms"] <= 52
    assert 94 <= s["p95_ms"] <= 96
    assert s["max_ms"] == 100.0
    assert list(s) == ["count", "mean_ms", "p50_ms", "p95_ms",
                       "p99_ms", "max_ms"]


def test_histogram_never_tears_count_total_pair():
    """The /stats bugfix contract: a summary taken from ANY thread mid-
    record reads a coherent (count, total) pair — with every sample a
    constant, mean_ms can never drift off that constant."""
    h = Histogram()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            h.observe(0.005)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 0.5
        seen = 0
        while time.monotonic() < deadline:
            s = h.summary()
            if s["count"]:
                seen += 1
                assert s["mean_ms"] == pytest.approx(5.0, abs=1e-6), s
        assert seen > 0
    finally:
        stop.set()
        for t in threads:
            t.join(5)


def test_prometheus_rendering_parses_and_names_stably():
    reg = Registry()
    reg.counter("serve_completed").inc(5)
    reg.gauge("mem_bytes_in_use_dev0").set(2e9)
    h = reg.histogram("serve_e2e_latency")
    for _ in range(10):
        h.observe(0.002)
    text = reg.render_prometheus()
    lines = [ln for ln in text.splitlines() if ln.strip()]
    bad = [ln for ln in lines if not ln.startswith("#")
           and not _SAMPLE_RE.match(ln)]
    assert not bad, bad
    assert "# TYPE serve_completed_total counter" in lines
    assert "serve_completed_total 5" in lines
    assert "# TYPE mem_bytes_in_use_dev0 gauge" in lines
    assert "# TYPE serve_e2e_latency summary" in lines
    assert 'serve_e2e_latency{quantile="0.5"} 0.002' in lines
    assert "serve_e2e_latency_count 10" in lines
    # summary samples are base-unit seconds (sum = 10 * 2ms)
    sum_line = [ln for ln in lines
                if ln.startswith("serve_e2e_latency_sum")][0]
    assert float(sum_line.split()[1]) == pytest.approx(0.02)


# -------------------------------------------------------------- tracing


def test_tracer_disabled_is_noop_and_enabled_records_depth():
    tr = Tracer()
    with tr.span("x"):
        pass
    assert len(tr) == 0  # disabled: nothing recorded, shared noop span

    tr.enable()
    with tr.span("outer"):
        with tr.span("inner"):
            time.sleep(0.002)
    evs = tr.chrome_events()
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"outer", "inner"}
    assert xs["outer"]["args"]["depth"] == 0
    assert xs["inner"]["args"]["depth"] == 1
    assert xs["inner"]["dur"] >= 2000  # us
    # inner nests inside outer on the same thread
    assert xs["inner"]["tid"] == xs["outer"]["tid"]
    assert xs["outer"]["ts"] <= xs["inner"]["ts"]
    assert [e for e in evs if e["ph"] == "M"
            and e["name"] == "thread_name"]


def _profiled_host_events(tmp_path, body) -> list:
    """Run ``body`` on a worker thread under a ``jax.profiler`` trace
    with the Python tracer off (as the benchmark's traced serving run
    has it); -> the host plane's events as (name, start, end, stats)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        t = threading.Thread(target=body)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events]
    return events


def test_a_span_is_a_profiler_annotation_named_cat_slash_name(tmp_path):
    """Ring off, no sink: under a running profile a span still lands in
    the profile's host events as ``<cat>/<name>`` with its scalar args
    as stats, on the device trace's clock."""
    tr = Tracer()

    def body():
        with tr.span("pack", cat="serve",
                     args={"model": "m", "bucket": 64, "rows": 40,
                           "traces": ["not", "a", "scalar"]}):
            time.sleep(0.003)

    events = _profiled_host_events(tmp_path, body)
    (ev,) = [e for e in events if e[0] == "serve/pack"]
    assert ev[3] == {"model": "m", "bucket": 64, "rows": 40}
    assert ev[2] - ev[1] >= 3e6     # ns
    assert len(tr) == 0             # the ring stayed off


def test_an_enclosing_span_stays_off_the_profile(tmp_path):
    """``encloses=True`` (train/epoch, train/eval): the ring gets the
    span, the profile only its children, so that no host event covers
    them when idle gaps are named."""
    tr = Tracer().enable()

    def body():
        with tr.span("epoch", cat="train", encloses=True):
            for _ in range(2):
                with tr.span("step", cat="train"):
                    time.sleep(0.002)

    events = _profiled_host_events(tmp_path, body)
    names = [e[0] for e in events]
    assert names.count("train/step") == 2
    assert "train/epoch" not in names
    steps = [e for e in events if e[0] == "train/step"]
    lo, hi = min(e[1] for e in steps), max(e[2] for e in steps)
    assert not [e for e in events if e[0].startswith("train/")
                and e[1] <= lo and e[2] >= hi]
    ring = {e["name"] for e in tr.chrome_events() if e["ph"] == "X"}
    assert ring == {"epoch", "step"}


def test_span_builds_nothing_with_ring_and_profile_off(monkeypatch):
    from deepvision_tpu.obs import trace

    built = []
    real = trace.Span.__init__
    monkeypatch.setattr(
        trace.Span, "__init__",
        lambda self, *a, **k: (built.append(a), real(self, *a, **k))[1])
    tr = Tracer()
    assert tr.span("x", cat="serve") is tr.span("y", encloses=True)
    assert not built                # the shared no-op, no Span
    with tr.timed("z", cat="serve") as sp:  # always measures
        pass
    assert len(built) == 1 and sp.dur >= 0 and len(tr) == 0


def test_timed_span_feeds_observer_ring_and_caller_one_measurement():
    tr = Tracer().enable()
    seen = []
    with tr.timed("pack", cat="serve", args={"rows": 3},
                  observe=seen.append) as sp:
        time.sleep(0.001)
    ((name, _cat, _ts, dur, *_rest),) = list(tr._events)
    assert name == "pack" and seen == [sp.dur] and dur == sp.dur


def test_tracer_export_chrome_format_and_threads(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.span("main_work", cat="train"):
        t = threading.Thread(
            target=lambda: tr.span("bg_work", cat="feed").__enter__()
            .__exit__(None, None, None))
        t.start()
        t.join()
    out = tmp_path / "trace.json"
    n = tr.export(out)
    assert n == 2
    data = json.loads(out.read_text())
    xs = [e for e in data["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"main_work", "bg_work"}
    tids = {e["tid"] for e in xs}
    assert len(tids) == 2  # thread-aware: separate tracks
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0  # monotonic, microseconds


def test_span_device_sync_blocks_before_end_stamp():
    import jax.numpy as jnp

    tr = Tracer()
    tr.enable()
    with tr.span("step") as sp:
        y = jnp.ones((8, 8)) * 2.0
        assert sp.device_sync(y) is y  # returns the value for chaining
    (ev,) = [e for e in tr.chrome_events() if e["ph"] == "X"]
    assert ev["name"] == "step" and ev["dur"] > 0


def test_summarize_chrome_attribution_union_no_double_count():
    pid = 1
    mk = lambda name, ts, dur, tid=10: {  # noqa: E731
        "name": name, "ph": "X", "ts": ts * 1e3, "dur": dur * 1e3,
        "pid": pid, "tid": tid, "args": {}}
    events = [
        mk("epoch", 0, 100),
        mk("step", 0, 40),
        mk("fetch", 30, 30),      # overlaps step: union is [0, 60)
        mk("other_thread", 0, 100, tid=99),  # not a wall thread
        mk("step", 200, 10),      # outside the wall window: clipped away
    ]
    s = summarize_chrome(events, wall_span="epoch")
    assert s["wall_ms"] == pytest.approx(100.0)
    assert s["attributed_ms"] == pytest.approx(60.0)
    assert s["coverage"] == pytest.approx(0.6)
    assert s["spans"]["step"]["count"] == 2
    assert s["spans"]["step"]["total_ms"] == pytest.approx(50.0)
    # no wall span in the trace: full extent becomes the wall
    s2 = summarize_chrome([mk("step", 0, 40), mk("fetch", 40, 10)],
                          wall_span="epoch")
    assert s2["wall_ms"] == pytest.approx(50.0)
    assert s2["coverage"] == pytest.approx(1.0)


def test_trace_summary_cli_asserts_spans_and_coverage(tmp_path):
    from tools.trace_summary import main as ts_main

    events = [
        {"name": "epoch", "ph": "X", "ts": 0.0, "dur": 100e3,
         "pid": 1, "tid": 1, "args": {}},
        {"name": "step", "ph": "X", "ts": 0.0, "dur": 98e3,
         "pid": 1, "tid": 1, "args": {}},
    ]
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"traceEvents": events}))
    assert ts_main([str(p), "--assert-spans", "step",
                    "--min-coverage", "0.95"]) == 0
    assert ts_main([str(p), "--assert-spans", "fetch"]) == 1
    assert ts_main([str(p), "--min-coverage", "0.999"]) == 1
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"traceEvents": []}))
    assert ts_main([str(empty)]) == 1


# ------------------------------------------------------------- profiler


def test_device_memory_stats_graceful_and_gauged():
    from deepvision_tpu.obs.profiler import (
        device_memory_stats,
        sample_memory_gauges,
    )

    stats = device_memory_stats()  # CPU backend: usually {}
    assert isinstance(stats, dict)
    assert all(k.startswith("mem_") for k in stats)
    reg = Registry()
    out = sample_memory_gauges(reg)
    assert out == stats
    for k, v in out.items():
        assert reg.snapshot()[k] == v
    if not stats:  # the CPU-container caveat: no gauges invented
        assert reg.names() == []


def test_device_memory_stats_reads_the_reserved_peak(monkeypatch):
    """`peak_bytes_in_use` counts live arrays only; the temporaries of a
    running executable are in `peak_bytes_reserved`, which the gauges
    left out (they under-read a ResNet-50 b256 process by 8.8 GB)."""
    import jax

    from deepvision_tpu.obs import profiler

    class _Dev:
        def memory_stats(self):
            return {"bytes_in_use": 1.0, "peak_bytes_in_use": 2.0,
                    "bytes_limit": 9.0, "peak_bytes_reserved": 7.0,
                    "num_allocs": 3}

    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev()])
    assert profiler.device_memory_stats() == {
        "mem_bytes_in_use_dev0": 1.0, "mem_peak_bytes_in_use_dev0": 2.0,
        "mem_bytes_limit_dev0": 9.0, "mem_peak_bytes_reserved_dev0": 7.0}


def test_profile_window_start_stop_and_spec_validation(monkeypatch):
    from deepvision_tpu.obs import profiler as prof

    calls = []
    monkeypatch.setattr(
        "jax.profiler.start_trace", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(
        "jax.profiler.stop_trace", lambda: calls.append(("stop",)))

    w = prof.ProfileWindow("2:4", "/tmp/obs_test_profile")
    for step in range(8):
        w.on_step(step)
    assert [c[0] for c in calls] == ["start", "stop"]
    assert w.done and not w.active
    w.on_step(2)  # once per run: a later window never reopens
    assert [c[0] for c in calls] == ["start", "stop"]

    calls.clear()
    w2 = prof.ProfileWindow("6:6", "/tmp/obs_test_profile")
    w2.on_step(6)
    w2.close()  # run ended inside the window: close() stops the trace
    assert [c[0] for c in calls] == ["start", "stop"]

    for bad in ("x:y", "3", "5:2", "-1:4"):
        with pytest.raises(ValueError):
            prof.ProfileWindow(bad, "/tmp/p")


def test_profile_window_degrades_when_profiler_unavailable(monkeypatch):
    from deepvision_tpu.obs import profiler as prof

    def boom(d):
        raise RuntimeError("no profiler in this build")

    monkeypatch.setattr("jax.profiler.start_trace", boom)
    w = prof.ProfileWindow("0:1", "/tmp/obs_test_profile")
    w.on_step(0)  # must not raise
    assert w.done and not w.active


# ----------------------------------- byte-compat of refactored surfaces


def test_serve_telemetry_snapshot_keys_and_registry_names():
    from deepvision_tpu.serve import LatencyStats, ServeTelemetry

    reg = Registry()
    tel = ServeTelemetry(registry=reg)
    tel.record_submit()
    tel.record_batch(bucket=4, rows=3, device_s=0.004)
    tel.record_request(queue_wait_s=0.001, e2e_s=0.006)
    snap = tel.snapshot()
    # the exact PR 3 /stats shape, key order included
    assert list(snap)[:15] == [
        "submitted", "completed", "timed_out", "failed", "shed",
        "batches", "rows", "padded_rows", "dispatcher_crashes",
        "dispatcher_restarts", "pad_overhead_frac", "mean_batch_rows",
        "queue_wait", "device_time", "e2e_latency",
    ]
    # then the dispatcher's phases (PR 25): keys gained, none lost
    assert list(snap)[15:20] == [
        "wait_time", "fill_window_time", "pack_time", "device_put_time",
        "resolve_time"]
    # then the staging buffers' counters and gauge (PR 26)
    assert list(snap)[20:] == [
        "stage_allocs", "stage_reuses", "stage_bytes"]
    assert snap["pad_overhead_frac"] == 0.25
    # attribute-style reads (engine/tests rely on these)
    assert tel.submitted == 1 and tel.batches == 1 and tel.rows == 3
    # one registry, stable serve_* names
    rs = reg.snapshot()
    assert rs["serve_submitted"] == 1
    assert rs["serve_e2e_latency"]["count"] == 1
    assert {"serve_queue_wait", "serve_device_time",
            "serve_dispatcher_crashes"} <= set(reg.names())
    # LatencyStats stays a drop-in reservoir wrapper
    ls = LatencyStats()
    ls.record(0.5)
    assert ls.count == 1 and ls.total_s == pytest.approx(0.5)


def test_feed_telemetry_accumulator_compat_and_registry_names():
    from deepvision_tpu.data.prefetch import FeedTelemetry

    reg = Registry()
    tel = FeedTelemetry(registry=reg)
    tel.host_wait_s += 0.1   # the producer thread's += idiom
    tel.host_wait_s += 0.2
    tel.h2d_wait_s = 0.3     # plain assignment (test/bench idiom)
    tel.step_s, tel.batches = 0.1, 10
    snap = tel.snapshot()
    assert snap == {"host_wait_s": pytest.approx(0.3), "shard_s": 0.0,
                    "h2d_wait_s": pytest.approx(0.3),
                    "step_s": pytest.approx(0.1), "batches": 10}
    s = tel.summary()
    assert s["input_wait_frac"] == pytest.approx(0.75)
    assert s["h2d_wait_ms"] == pytest.approx(30.0)
    # summary(since=...) delta math is unchanged
    base = tel.snapshot()
    tel.step_s += 0.4
    tel.batches += 2
    d = tel.summary(since=base)
    assert d["batches"] == 2
    assert d["step_ms"] == pytest.approx(200.0)
    # registry carries the per-batch stage histograms + batch counter
    rs = reg.snapshot()
    assert rs["input_batches"] == 12
    assert rs["input_host_wait"]["count"] == 2  # one sample per +=
    tel.reset()
    assert tel.snapshot()["batches"] == 0
    assert reg.snapshot()["input_host_wait"]["count"] == 0


def test_recovery_counters_compat_and_registry_names():
    from deepvision_tpu.resilience import RecoveryCounters

    reg = Registry()
    c = RecoveryCounters(registry=reg)
    c.inc("rollbacks")
    c.inc("data_retries", 2)
    assert c.get("rollbacks") == 1
    assert c.snapshot() == {"rollbacks": 1, "ckpt_fallbacks": 0,
                            "data_retries": 2, "lr_rewarms": 0}
    # the grep-stable chaos-gate line, field order included
    assert c.format() == ("rollbacks=1 ckpt_fallbacks=0 "
                          "data_retries=2 lr_rewarms=0")
    with pytest.raises(KeyError):
        c.inc("nonsense")
    assert reg.snapshot()["recovery_data_retries"] == 2


def test_default_registry_carries_all_four_namespaces():
    """The tentpole claim: train-feed, serve, recovery (and mem_* when
    on-chip) all register into ONE process registry by default."""
    from deepvision_tpu.data.prefetch import FeedTelemetry
    from deepvision_tpu.resilience import RecoveryCounters
    from deepvision_tpu.serve import ServeTelemetry

    FeedTelemetry()
    ServeTelemetry()
    RecoveryCounters()
    names = set(default_registry().names())
    assert {"input_host_wait", "input_batches", "serve_submitted",
            "serve_e2e_latency", "recovery_rollbacks"} <= names


# ------------------------------------ loggers coverage (train/loggers)


def test_input_wait_and_recovery_metrics_key_prefix_contracts():
    from deepvision_tpu.resilience import RecoveryCounters
    from deepvision_tpu.train.loggers import (
        input_wait_metrics,
        recovery_metrics,
    )

    m = input_wait_metrics({"host_wait_ms": 1.0, "shard_ms": 2.0,
                            "h2d_wait_ms": 3.0, "step_ms": 4.0,
                            "input_wait_frac": 0.5, "batches": 9})
    assert set(m) == {"input_host_wait_ms", "input_shard_ms",
                      "input_h2d_wait_ms", "input_step_ms",
                      "input_wait_frac"}  # batches never leaks through
    assert all(k.startswith("input_") for k in m)
    assert all(isinstance(v, float) for v in m.values())

    c = RecoveryCounters(registry=Registry())
    c.inc("ckpt_fallbacks")
    r = recovery_metrics(c)
    assert set(r) == {"recovery_rollbacks", "recovery_ckpt_fallbacks",
                      "recovery_data_retries", "recovery_lr_rewarms"}
    assert r["recovery_ckpt_fallbacks"] == 1.0
    # plain-dict snapshots flatten identically
    assert recovery_metrics({"rollbacks": 3}) == {
        "recovery_rollbacks": 3.0}


def test_loggers_json_roundtrip_and_latest():
    from deepvision_tpu.train.loggers import Loggers

    lg = Loggers(metrics=["train_loss"])
    lg.log_metrics(0, {"train_loss": 1.5, "val_top1": 0.1})
    lg.log_metrics(1, {"train_loss": 1.2})
    back = Loggers.from_json(lg.to_json())
    assert back.data == lg.data
    assert back.latest("train_loss") == 1.2
    assert back.latest("val_top1") == 0.1
    assert back.latest("absent") is None


def test_loggers_checkpoint_ride_along_roundtrip(tmp_path):
    """save -> restore keeps the metric history inside the checkpoint
    (the reference keeps its curves there too) — previously only
    exercised indirectly through full Trainer runs."""
    import optax

    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.checkpoint import CheckpointManager
    from deepvision_tpu.train.loggers import Loggers
    from deepvision_tpu.train.state import create_train_state

    state = create_train_state(get_model("lenet5"), optax.sgd(0.1),
                               np.zeros((1, 32, 32, 1), np.float32))
    lg = Loggers()
    lg.log_metrics(-1, {"val_loss": 2.3})
    lg.log_metrics(0, {"train_loss": 1.9, "input_h2d_wait_ms": 0.4,
                       "recovery_rollbacks": 0.0})
    mgr = CheckpointManager(tmp_path / "ck")
    try:
        mgr.save(0, state, loggers=lg)
        _, meta = mgr.restore(state)
        restored = meta["loggers"]
        assert isinstance(restored, Loggers)
        assert restored.data == lg.data  # histories equal, epochs incl.
        assert restored.latest("train_loss") == 1.9
    finally:
        mgr.close()


# -------------------------------------------------- /metrics HTTP leg


def test_metrics_endpoint_renders_live_engine(tmp_path):
    """GET /metrics on the serve handler: exposition-format text whose
    serve_* families reflect the live engine (the in-process version of
    the make obs-smoke curl leg, on the toy model)."""
    import http.server
    import urllib.request
    from argparse import Namespace

    import serve as serve_cli
    from tests.test_serve import make_engine

    with make_engine() as eng:
        eng.submit(np.zeros(3, np.float32)).result(timeout=30)
        handler = serve_cli.make_handler(eng, Namespace(timeout_s=10.0))
        server = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                                 handler)
        threading.Thread(target=server.serve_forever,
                         daemon=True).start()
        try:
            url = (f"http://127.0.0.1:{server.server_address[1]}"
                   "/metrics")
            with urllib.request.urlopen(url, timeout=30) as r:
                assert "text/plain" in r.headers.get("Content-Type", "")
                body = r.read().decode()
            lines = [ln for ln in body.splitlines() if ln.strip()]
            bad = [ln for ln in lines if not ln.startswith("#")
                   and not _SAMPLE_RE.match(ln)]
            assert not bad, bad
            samples = {ln.split(" ")[0]: float(ln.rsplit(" ", 1)[1])
                       for ln in lines if not ln.startswith("#")}
            assert samples["serve_completed_total"] >= 1
            assert samples["serve_e2e_latency_count"] >= 1
            assert 'serve_e2e_latency{quantile="0.99"}' in samples
            # the dispatcher's phases (PR 25) beside serve_device_time
            for phase in ("wait", "fill_window", "pack", "device_put",
                          "resolve"):
                assert f"serve_{phase}_time_count" in samples, phase
            for phase in ("pack", "device_put", "device", "resolve"):
                assert samples[f"serve_{phase}_time_count"] >= 1, phase
        finally:
            server.shutdown()
            server.server_close()
