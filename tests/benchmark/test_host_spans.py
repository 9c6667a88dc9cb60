"""The reduction of the program's spans and scopes against the trace
recorded beside it (``benchmark/reduce/record_spans.py`` on one v5e
chip, PR 25): three batches of the tests' tiny serving configuration
(4, 1 and 3 rows in the bucket of 4) with 0.12 s of nothing offered
after the first; and the four readers and eight metrics that read it.
(The recording's executable is still called ``jit_forward``; the scopes
and spans that the reduction reads are today's.)"""

import shutil
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells
from benchmark.reduce import host_spans, xplane

HERE = Path(host_spans.__file__).parent
RECORDED = HERE / "recorded_spans.xplane.pb"
WITHOUT_SPANS = HERE / "recorded_tiny.xplane.pb"      # PR 23's program
PHASES = ("wait", "fill_window", "pack", "device_put", "device", "resolve")
NEW_METRICS = (
    "host_pack_ms.serve", "host_device_put_ms.serve",
    "host_resolve_ms.serve", "idle_host_work_pct.serve",
    "idle_transfer_pct.serve", "idle_no_work_pct.serve",
    "nms_time_pct.serve", "forward_mfu_pct.serve")
# as the serving driver hands them to a reader on a v5e
FACTS = {"serve": {"flops_per_image": 3.5e9, "chips": 1,
                   "peak_flops": 197e12}}


@pytest.fixture(scope="module")
def reduced():
    return host_spans.reduce(str(RECORDED))


def _as_the_traced_run(monkeypatch, tmp_path, recorded,
                       cell="yolov3.serve_steady"):
    """Lay ``recorded`` where a traced run of ``cell`` leaves its
    profile."""
    d = tmp_path / cell / "plugins" / "profile" / "2026_09_30"
    d.mkdir(parents=True)
    shutil.copy(recorded, d / "host.xplane.pb")
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)


def test_window_and_busy_are_the_device_reductions(reduced):
    plain = xplane.reduce(str(RECORDED))
    assert reduced["window_s"] == pytest.approx(plain["window_s"])
    assert reduced["busy_s"] == pytest.approx(plain["busy_s"])
    assert reduced["window_s"] == pytest.approx(139.405735e-3, rel=1e-6)
    assert reduced["idle_s"] == pytest.approx(132.459753e-3, rel=1e-6)


def test_the_phases_of_three_batches(reduced):
    counts = {n: reduced["phases"][f"serve/{n}"]["count"] for n in PHASES}
    assert counts == {"wait": 4, "fill_window": 3, "pack": 3,
                      "device_put": 3, "device": 3, "resolve": 3}
    pack = reduced["phases"]["serve/pack"]
    assert pack["mean_s"] == pytest.approx(354.24e-6, rel=1e-4)
    assert pack["total_s"] == pytest.approx(3 * pack["mean_s"])


def test_the_idle_split_sums_to_the_idle_time(reduced):
    under = sum(p["idle_s"] for p in reduced["phases"].values())
    assert under + reduced["idle_unattributed_s"] == pytest.approx(
        reduced["idle_s"], rel=1e-9)
    # what lies between two phases is microseconds: well under the 5% of
    # the idle time that the metrics' sum may miss device_idle_pct by
    assert reduced["idle_unattributed_s"] < 0.005 * reduced["idle_s"]
    # nothing was offered for 0.12 s: the chip idled under serve/wait
    assert reduced["phases"]["serve/wait"]["idle_s"] == pytest.approx(
        121.185726e-3, rel=1e-6)
    # the chip idles for all of a phase that runs nothing on it
    for name in ("serve/wait", "serve/fill_window"):
        phase = reduced["phases"][name]
        assert phase["idle_s"] == pytest.approx(phase["total_s"], rel=2e-3)
    # and inside its own device span while the batch travels
    device = reduced["phases"]["serve/device"]
    assert 0.2 < device["idle_s"] / device["total_s"] < 0.4


def test_each_execution_carries_its_rows_and_module_time(reduced):
    runs = reduced["executions"]
    assert [(e["rows"], e["bucket"]) for e in runs] == [(4, 4), (1, 4),
                                                       (3, 4)]
    for e in runs:      # one launch of the bucket-4 program a span
        assert e["module_s"] == pytest.approx(2.317e-3, rel=1e-3)
        assert e["module_s"] <= e["span_s"]


def test_busy_time_by_named_scope(reduced):
    scopes = reduced["scope_busy_s"]
    assert scopes["served/postprocess"] == pytest.approx(5.514716e-3,
                                                         rel=1e-6)
    assert scopes["served/forward"] == pytest.approx(0.73672e-3, rel=1e-5)
    # every operation but the input's relayout is in one of the halves
    assert sum(scopes.values()) == pytest.approx(reduced["busy_s"],
                                                 rel=0.1)
    ops = host_spans.read_op_scopes(str(RECORDED))["/device:TPU:0"]
    assert any("/served/forward/YoloV3/backbone/" in v
               for v in ops.values())


def _ms(x):
    return int(x * 1e6)


def test_a_gap_across_two_phases_is_split_and_the_cut_holds():
    ops = [(_ms(0), _ms(10), "%fusion.1 = f32[] fusion(), kind=kOutput"),
           (_ms(40), _ms(10), "%sort.2 = f32[] sort()"),
           (_ms(900), _ms(10), "%add.3 = f32[] add()")]    # after the stop
    modules = [(_ms(0), _ms(10), "jit_served_forward(1)"),
               (_ms(40), _ms(10), "jit_served_forward(1)")]
    host = [(_ms(60), _ms(500), "$profiler.py:213 stop_trace")]
    spans = [(_ms(-2), _ms(14), "serve/device", {"rows": 3, "bucket": 4}),
             (_ms(12), _ms(8), "serve/resolve", {"rows": 3}),
             (_ms(20), _ms(12), "serve/pack", {"rows": 2, "bucket": 4}),
             (_ms(35), _ms(20), "serve/device", {"rows": 2, "bucket": 4}),
             (_ms(55), _ms(600), "serve/resolve", {"rows": 2})]  # cut
    scopes = {"/device:TPU:0": {
        ops[0][2]: "jit(served_forward)/served/forward/conv_general_dilated:",
        ops[1][2]: "jit(served_forward)/served/postprocess/sort:"}}
    out = host_spans.reduce_spans(
        {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
         "host": host}, spans, scopes)
    assert out["window_s"] == pytest.approx(0.050)
    assert out["idle_s"] == pytest.approx(0.030)
    # the one gap, 10..40 ms: 2 under device, 8 under resolve, 12 under
    # pack, 5 under the next device span, 3 under none
    idle = {n: p["idle_s"] for n, p in out["phases"].items()}
    assert idle == pytest.approx({"serve/device": 0.007,
                                  "serve/resolve": 0.008,
                                  "serve/pack": 0.012})
    assert out["idle_unattributed_s"] == pytest.approx(0.003)
    assert out["phases"]["serve/resolve"]["count"] == 1     # the cut
    assert [(e["rows"], e["module_s"]) for e in out["executions"]] == [
        (3, pytest.approx(0.010)), (2, pytest.approx(0.010))]
    assert out["scope_busy_s"] == pytest.approx(
        {"served/forward": 0.010, "served/postprocess": 0.010})


def test_a_trace_without_spans_reads_nothing(monkeypatch, tmp_path):
    assert host_spans.reduce(str(WITHOUT_SPANS)) is None
    _as_the_traced_run(monkeypatch, tmp_path, WITHOUT_SPANS)
    for name in NEW_METRICS:
        spec = cells.metric_file(name)
        assert cells.reader_for(spec).read(FACTS, spec) is None, name


def test_no_trace_at_all_reads_nothing(monkeypatch, tmp_path):
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path / "absent")
    for name in NEW_METRICS:
        spec = cells.metric_file(name)
        assert cells.reader_for(spec).read(FACTS, spec) is None, name


def test_the_readers_on_the_recorded_trace(monkeypatch, tmp_path, reduced):
    _as_the_traced_run(monkeypatch, tmp_path, RECORDED)
    got = {}
    for name in NEW_METRICS:
        spec = cells.metric_file(name)
        got[name] = cells.reader_for(spec).read(FACTS, spec)
    assert got["host_pack_ms.serve"] == pytest.approx(0.35424, rel=1e-4)
    assert got["host_device_put_ms.serve"] == pytest.approx(0.51934,
                                                            rel=1e-4)
    assert got["host_resolve_ms.serve"] == pytest.approx(0.197997,
                                                         rel=1e-4)
    idle_pct = 100.0 * reduced["idle_s"] / reduced["window_s"]
    split = (got["idle_host_work_pct.serve"]
             + got["idle_transfer_pct.serve"]
             + got["idle_no_work_pct.serve"])
    assert split == pytest.approx(idle_pct, rel=0.05)
    assert split <= idle_pct
    assert got["idle_no_work_pct.serve"] == pytest.approx(86.93, abs=0.01)
    assert got["idle_transfer_pct.serve"] == pytest.approx(3.72, abs=0.01)
    assert got["nms_time_pct.serve"] == pytest.approx(79.39, abs=0.01)
    # 8 useful rows x 3.5 GFLOP over 3 x 2.317 ms at 197 TFLOP/s
    assert got["forward_mfu_pct.serve"] == pytest.approx(
        100 * 8 * 3.5e9 / (6.951441e-3 * 197e12), rel=1e-4)
    assert got["forward_mfu_pct.serve"] <= 100.0


def test_forward_mfu_needs_a_peak(monkeypatch, tmp_path):
    _as_the_traced_run(monkeypatch, tmp_path, RECORDED)
    spec = cells.metric_file("forward_mfu_pct.serve")
    facts = {"serve": {**FACTS["serve"], "peak_flops": None}}   # a CPU
    assert cells.reader_for(spec).read(facts, spec) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_file_is_found_and_equals_its_entry(name):
    spec = cells.metric_file(name)
    (entry,) = [m for m in cells.load_spec()["per_layer"]
                if m["name"] == name]
    assert entry["workloads"] == ["yolov3.serve_steady"]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], (name, key)
    assert hasattr(cells.reader_for(spec), "read") and spec["what"]
    cell = cells.load_cell("yolov3.serve_steady")
    assert name in [m["name"] for m in cell.per_layer]
    resident = cells.load_cell("resnet50.train_resident")
    assert name not in [m["name"] for m in resident.per_layer]


def test_the_accepted_metrics_stand_beside_the_new():
    """Whatever later PRs append, and in whatever order the list then
    is: PR 23's nine are there, and each of PR 25's is there once."""
    names = [m["name"] for m in cells.load_spec()["per_layer"]]
    for name in ("compile_s", "step_mfu_pct.train", "conv_time_pct.train",
                 "device_idle_pct.train", "step_mfu_pct.serve",
                 "queue_wait_ms.serve", "batch_rows_mean.serve",
                 "conv_time_pct.serve", "device_idle_pct.serve",
                 *NEW_METRICS):
        assert names.count(name) == 1, name


def test_a_reader_takes_its_own_cell_s_profile(monkeypatch, tmp_path):
    """Another cell's left-over profile, though newer, is not this
    run's: the metric's ``workloads`` say whose directory to read."""
    spec = cells.metric_file("host_pack_ms.serve")
    _as_the_traced_run(monkeypatch, tmp_path, RECORDED)
    own = host_spans.newest_trace(spec)
    _as_the_traced_run(monkeypatch, tmp_path, WITHOUT_SPANS, "another.cell")
    assert host_spans.newest_trace(spec) == own
    assert "yolov3.serve_steady" in own
    assert cells.reader_for(spec).read(FACTS, spec) == pytest.approx(
        0.35424, rel=1e-4)
    shutil.rmtree(tmp_path / "yolov3.serve_steady")
    assert host_spans.newest_trace(spec) is None
    # a metric that lists no cells reads the newest of any
    assert host_spans.newest_trace({"name": "compile_s"}) is not None


def test_one_name_in_two_scopes_is_given_to_neither(tmp_path):
    from benchmark.reduce.record_spans import _put, _put_int

    def stat(meta_id, ref):
        out = bytearray()
        _put_int(out, 1, meta_id)
        _put_int(out, 7, ref)
        return out

    def entry(key, message):
        out = bytearray()
        _put_int(out, 1, key)
        _put(out, 2, message)
        return out

    def named(name, *stats):
        out = bytearray()
        _put(out, 2, name.encode())
        for s in stats:
            _put(out, 5, s)
        return out

    plane = bytearray()
    _put(plane, 2, b"/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "jit(f)/served/forward/conv:"),
                      (3, "jit(f)/served/postprocess/sort:")):
        _put(plane, 5, entry(key, named(name)))
    for key, name, ref in ((1, "%a", 2), (2, "%a", 3), (3, "%b", 3),
                           (4, "%b", 3)):
        _put(plane, 4, entry(key, named(name, stat(1, ref))))
    space = bytearray()
    _put(space, 1, plane)
    path = tmp_path / "two_programs.xplane.pb"
    path.write_bytes(space)
    assert host_spans.read_op_scopes(str(path)) == {"/device:TPU:0": {
        "%a": "", "%b": "jit(f)/served/postprocess/sort:"}}


def test_the_recording_keeps_only_the_program_s_host_events():
    from jax.profiler import ProfileData

    from benchmark.reduce import record_spans

    data = RECORDED.read_bytes()
    assert record_spans.slim(data) == data        # as the recorder left it
    names = {plane.name: [e.name for line in plane.lines
                          for e in line.events]
             for plane in ProfileData.from_file(str(RECORDED)).planes}
    assert "/host:metadata" not in names
    assert len(names["/host:CPU"]) == 19
    assert all(n.startswith("serve/") for n in names["/host:CPU"])
