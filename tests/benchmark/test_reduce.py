"""The trace reduction against the small trace recorded beside it: four
launches of a two-convolution program on one v5e chip (PR 23)."""

from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.reduce import xplane

RECORDED = (Path(xplane.__file__).parent / "recorded_tiny.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(str(RECORDED))


def test_union_merges_overlaps_and_reports_gaps():
    busy, gaps = xplane.union([(0, 10), (5, 12), (20, 30), (30, 31)])
    assert busy == 12 + 11
    assert gaps == [(12, 20)]


def test_busy_is_the_union_of_the_ops(reduced):
    trace = xplane.read(str(RECORDED))
    (dev,) = trace["devices"].values()
    assert len(dev["ops"]) == 32 and len(dev["modules"]) == 4
    # the ops of this program do not overlap: union == sum
    assert reduced["busy_s"] == pytest.approx(
        sum(d for _, d, _ in dev["ops"]) / 1e9)
    assert reduced["busy_s"] == pytest.approx(169.929e-6, rel=1e-6)


def test_idle_share_of_the_recorded_window(reduced):
    assert reduced["window_s"] == pytest.approx(3351.389e-6, rel=1e-6)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.9493, abs=1e-4)
    named = dict(reduced["idle_gaps"])
    # 2 ms of sleep between launches 2 and 3, 0.9 ms more around it
    assert sum(named.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_convolution_share(reduced):
    # fusion.15 and convert_reduce_fusion are the two kOutput fusions
    assert reduced["conv_s"] / reduced["busy_s"] == pytest.approx(
        0.7579, abs=1e-3)
    assert xplane.is_convolution(
        "%fusion.15 = bf16[8,64,64,32] fusion(...), kind=kOutput, calls=%f")
    assert not xplane.is_convolution(
        "%multiply_convert_fusion = bf16[8,64] fusion(...), kind=kLoop")


def test_top_ops_by_name(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert names[:3] == ["fusion.15", "convert_reduce_fusion", "copy"]
    assert xplane.op_name("%copy-done.1 = bf16[3,3] copy-done(...)") \
        == "copy-done.1"


def test_a_trace_without_device_ops_reads_nothing(tmp_path):
    assert xplane.newest_xplane(str(tmp_path)) is None


def _ms(x):
    return int(x * 1e6)


def test_gaps_are_named_after_the_host_and_cut_at_stop_trace():
    ops = [(_ms(0), _ms(10), "%fusion.1 = f32[] fusion(), kind=kOutput"),
           (_ms(30), _ms(10), "%add.2 = f32[] add()"),
           (_ms(45), _ms(5), "%add.2 = f32[] add()"),
           (_ms(900), _ms(10), "%add.2 = f32[] add()")]   # after the stop
    host = [(_ms(0), _ms(1000), "$threading.py:323 wait"),
            (_ms(9), _ms(19), "$engine.py:897 _run_batch"),
            (_ms(41), _ms(3), "device_put"),
            (_ms(60), _ms(500), "$profiler.py:213 stop_trace")]
    out = xplane.reduce_trace({"devices": {"/device:TPU:0": {
        "ops": ops, "modules": []}}, "host": host})
    assert out["window_s"] == pytest.approx(0.050)
    assert out["busy_s"] == pytest.approx(0.025)
    assert out["conv_s"] == pytest.approx(0.010)
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"$engine.py:897 _run_batch": 0.020, "device_put": 0.005})
    assert out["device_ops"][0] == ["add.2", pytest.approx(0.015)]
