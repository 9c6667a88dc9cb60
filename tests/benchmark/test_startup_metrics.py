"""The three start-up metrics (``setup_lower_s``, ``setup_runtime_s``,
``setup_programs``) and their reader, ``readers/startup_record.py``: the
program's own compile record and start-up spans, read in the run's
process and cut where the window opened."""

import time

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells
from deepvision_tpu import startup
from deepvision_tpu.obs import trace

NEW = ("setup_lower_s", "setup_runtime_s", "setup_programs")
CELLS = ["resnet50.train_resident", "yolov3.serve_steady",
         "keye_vl2_30b_a3b.train_seq8k"]


def _read(name: str, setup_s: float):
    spec = cells.metric_file(name)
    facts = {"end_to_end": {"setup_s": setup_s}}
    return cells.reader_for(spec).read(facts, spec)


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_file_is_found_and_equals_its_entry(name):
    spec = cells.metric_file(name)
    (entry,) = [m for m in cells.load_spec()["per_layer"]
                if m["name"] == name]
    assert entry["workloads"] == CELLS
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], (name, key)
    assert (spec["layer"], spec["moves"]) == ("start-up", "setup_s")
    assert spec["reader"] == "startup_record" and spec["what"]
    for cell in CELLS:
        assert name in [m["name"] for m in cells.load_cell(cell).per_layer]
    for cell in ("kanana2_30b_a3b.train_text8k", "xing4_29b_a4b.train_mtp"):
        assert name not in [m["name"]
                            for m in cells.load_cell(cell).per_layer]


@pytest.mark.parametrize("name", NEW)
def test_a_program_that_keeps_no_record_reads_nothing(monkeypatch, name):
    monkeypatch.setattr(startup, "_RECORD", None)
    assert _read(name, 30.0) is None


@pytest.fixture
def recorded(monkeypatch):
    """A record and a tracer of this test's own, with one runtime span
    and a program compiled before ``cut`` and one after it."""
    import jax
    import jax.numpy as jnp

    tr = trace.Tracer()
    monkeypatch.setattr(trace, "_TRACER", tr)
    rec = startup.CompileRecord().install()
    monkeypatch.setattr(startup, "_RECORD", rec)
    x = jnp.ones(3)
    with tr.startup("runtime"):
        time.sleep(0.002)

    def before_window(v):
        return jnp.tanh(v) * 4.0

    def after_window(v):
        return jnp.tanh(v) * 5.0

    try:
        jax.jit(before_window)(x)
        cut = time.perf_counter()
        time.sleep(0.02)
        jax.jit(after_window)(x)
        yield rec, cut - startup.process_start()
    finally:
        rec.uninstall()


def test_the_reader_reads_the_record_up_to_the_window(recorded):
    rec, setup_s = recorded
    whole = rec.summary()
    cut = rec.summary(until=startup.process_start() + setup_s)
    assert _read("setup_programs", setup_s) == cut["programs"] \
        == whole["programs"] - 1
    assert _read("setup_lower_s", setup_s) == pytest.approx(
        cut["trace_s"] + cut["lower_s"])
    assert 0 < _read("setup_lower_s", setup_s) < whole["trace_s"] \
        + whole["lower_s"]
    assert _read("setup_runtime_s", setup_s) >= 0.002


def test_a_window_opened_at_the_process_start_holds_nothing(recorded):
    assert _read("setup_programs", 0.0) == 0
    assert _read("setup_lower_s", 0.0) == 0.0
    assert _read("setup_runtime_s", 0.0) is None     # no such span yet
