"""A later PR adds a cell, a configuration, a traffic mix, a traffic
kind, a metric and a reader as files: the harness finds each by the name
in BENCHMARK.json, with no edit to a file that is there."""

import json
import time

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells, runner

from bench_helpers import CPU

DRIVER = '''
from benchmark.harness import checks

def run(run):
    work = run.cell.traffic["work"] * run.cell.config["size"]
    return {"end_to_end": {"things_per_s": work / run.seconds,
                           "setup_s": 0.25},
            "attempted": work, "failed": 0,
            "checks": [checks.Check("gap", run.reference.gap(), 0.5)],
            "memory_peak_bytes": 1, "compiles_in_window": 0,
            "custom": {"halves": work / 2}}
'''
READER = '''
def read(facts, spec):
    return facts.get(spec["section"], {}).get(spec["key"])
'''
REFERENCE = "def gap():\n    return 0.125\n"


def _write(root, rel, text):
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text if isinstance(text, str) else json.dumps(text))


def _drop_in(root):
    _write(root, "BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 10,
        "configs": [{"name": "newcfg", "source": "a paper",
                     "file": "benchmark/configs/newcfg.json",
                     "reduced": [], "why": "dropped in"}],
        "workloads": [{"name": "newcfg.newmix", "config": "newcfg",
                       "traffic": "newmix", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "things_per_s", "unit": "things/s", "better": "higher",
             "bound": 0.01, "source": "host_clock"},
            {"name": "other_cells_only", "unit": "s", "better": "lower",
             "bound": 0.01, "source": "host_clock", "workloads": ["x.y"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": [
            {"name": "halves", "unit": "things", "better": "higher",
             "source": "program_counter", "layer": "new layer",
             "moves": "things_per_s"},
            {"name": "nothing_to_read", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "new layer",
             "moves": "things_per_s"}]})
    _write(root, "benchmark/configs/newcfg.json",
           {"name": "newcfg", "size": 3, "reference": "newref"})
    _write(root, "benchmark/traffic/newmix.json",
           {"kind": "newkind", "work": 7})
    _write(root, "benchmark/drivers/newkind.py", DRIVER)
    _write(root, "benchmark/reference/newref.py", REFERENCE)
    _write(root, "benchmark/readers/section_key.py", READER)
    _write(root, "benchmark/metrics/halves.json",
           {"name": "halves", "reader": "section_key", "section": "custom",
            "key": "halves"})
    _write(root, "benchmark/metrics/nothing_to_read.json",
           {"name": "nothing_to_read", "reader": "section_key",
            "section": "absent", "key": "x"})


def test_dropped_in_files_are_found_by_name(tmp_path):
    _drop_in(tmp_path)
    cell = cells.load_cell("newcfg.newmix", tmp_path)
    assert cell.config["size"] == 3 and cell.traffic["work"] == 7
    assert [m["name"] for m in cell.end_to_end] == ["things_per_s",
                                                    "setup_s"]
    result = runner.execute(cell, seed=1, seconds=2.0, trace=False,
                            device=CPU, process_start=time.time(),
                            root=tmp_path)
    assert result["correct"] is True and result["attempted"] == 21
    assert result["metrics"] == {
        "things_per_s": {"value": 10.5, "unit": "things/s"},
        "setup_s": {"value": 0.25, "unit": "s"}}
    assert result["checks"]["gap"] == {"value": 0.125, "limit": 0.5,
                                       "ok": True}


def test_a_reader_with_nothing_to_read_leaves_its_metric_out(tmp_path):
    _drop_in(tmp_path)
    cell = cells.load_cell("newcfg.newmix", tmp_path)
    facts = {"custom": {"halves": 10.5}}
    values = {}
    for m in cell.per_layer:
        spec = cells.metric_file(m["name"], tmp_path)
        values[m["name"]] = cells.reader_for(spec, tmp_path).read(facts,
                                                                  spec)
    assert values == {"halves": 10.5, "nothing_to_read": None}


def test_an_unknown_cell_is_refused(tmp_path):
    _drop_in(tmp_path)
    try:
        cells.load_cell("newcfg.absent", tmp_path)
    except SystemExit as e:
        assert "unknown workload" in str(e)
    else:
        raise AssertionError("an unknown cell was accepted")


def test_the_fed_cells_wait_shares_read_the_feed_section():
    """The input pipeline's two metrics wait for ``resnet50.train_fed``
    (PERF.md section 7): their files and reader are in place, and a cell
    that feeds nothing leaves them out."""
    for name, key in (("input_host_wait_pct.train", "host_wait_s"),
                      ("input_h2d_wait_pct.train", "h2d_wait_s")):
        spec = cells.metric_file(name)
        reader = cells.reader_for(spec)
        assert spec["key"] == key and spec["moves"] == "train_img_per_s"
        facts = {"feed": {"host_wait_s": 12.0, "h2d_wait_s": 1.5,
                          "window_s": 30.0}}
        assert reader.read(facts, spec) == 100.0 * facts["feed"][key] / 30.0
        assert reader.read({"train": {}}, spec) is None
    with open(cells.ROOT / "benchmark" / "traffic" / "train_fed.json") as f:
        assert json.load(f)["kind"] == "train_fed"
