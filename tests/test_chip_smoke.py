"""Start-up rules that keep a chip run honest (ISSUE 21), checked on the
CPU: chip_smoke.py refuses to run without a TPU and its checkers reject
what an exit code hides; the compile cache has one resolution; one
process per chip; unknown chips have no assumed peaks."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

import chip_smoke
from deepvision_tpu import startup
from deepvision_tpu.ops.lrn import select_lrn_impl
from deepvision_tpu.serve.replica import (
    ReplicaDeadError,
    process_replica_factory,
)

REPO = Path(__file__).resolve().parent.parent
V5E = {"platform": "tpu", "kind": "TPU v5 lite"}


# ------------------------------------------------------- chip_smoke.py


def test_chip_smoke_without_a_chip_fails_fast_and_says_why():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "no TPU found" in p.stderr
    assert p.stdout.strip() == ""  # no result line of any kind


def _response(probs, classes=(1, 2, 3, 4, 5)) -> str:
    return json.dumps({"id": 0, "ms": 1.0, "result": {
        "classes": list(classes), "probs": list(probs)}})


def test_response_checker_accepts_a_softmax_top_k():
    got = chip_smoke.check_response(_response([0.5, 0.2, 0.1, 0.05, 0.01]))
    assert got["classes"] == [1, 2, 3, 4, 5]


@pytest.mark.parametrize("line, why", [
    # what serve.py prints for NaN weights — with exit code 0
    ('{"id": 0, "result": {"classes": [0, 1, 2, 3, 4], '
     '"probs": [NaN, NaN, NaN, NaN, NaN]}}', "not valid JSON"),
    # what it prints for a wrong-shaped request — also exit code 0
    ('{"id": 0, "error": "expects input shape (224, 224, 3)"}',
     "no result"),
    (_response([0.1, 0.5, 0.1, 0.05, 0.01]), "descending"),
    (_response([0.9, 0.9, 0.1, 0.05, 0.01]), "descending"),
    (_response([0.5, 0.2]), "top-5"),
    (_response([0.5, 0.2, 0.1, 0.05, 0.01], classes=(1, 2, 3, 4, 1000)),
     "classes outside"),
])
def test_response_checker_rejects(line, why):
    with pytest.raises(chip_smoke.SmokeFailure, match=why):
        chip_smoke.check_response(line)


_TRAIN_LOG = """\
[device] {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
mesh: {'data': 4, 'model': 1}
[feed] image (256, 224, 224, 3) float32: 4 shard(s) of (64, 224, 224, 3) \
on devices [0, 1, 2, 3]
[epoch 0 batch 0] loss=9.1844 running=9.1844
[epoch 0] train_loss=8.677 mem_bytes_in_use_dev0=1e+09 \
mem_peak_bytes_in_use_dev0=2e+09 val_loss=101.3
"""


def test_train_log_checker_reads_mesh_feed_and_losses():
    facts = chip_smoke.check_train_log(_TRAIN_LOG, devices=4, batch=256)
    assert facts["losses"] == [9.1844, 8.677, 101.3]
    # three of the four devices reported no memory: not a pass
    with pytest.raises(chip_smoke.SmokeFailure, match="dev1"):
        chip_smoke.check_memory_gauges(facts["metrics"], 4)


@pytest.mark.parametrize("old, new, why", [
    ("loss=9.1844", "loss=nan", "not finite"),
    ("'data': 4", "'data': 1", "mesh is not data=4"),
    ("[0, 1, 2, 3]", "[0, 0, 0, 0]", "not split evenly"),
    ("4 shard(s) of (64,", "1 shard(s) of (256,", "not split evenly"),
])
def test_train_log_checker_rejects(old, new, why):
    with pytest.raises(chip_smoke.SmokeFailure, match=why):
        chip_smoke.check_train_log(_TRAIN_LOG.replace(old, new),
                                   devices=4, batch=256)


# ------------------------------------------------------- compile cache


def test_cache_dir_set_outside_means_nothing_is_set_in_code():
    assert startup.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}) is None


def test_cache_dir_unset_is_one_fixed_path_inside_the_checkout():
    here = startup.compile_cache_dir({})
    assert here == startup.compile_cache_dir({}) == str(REPO / ".jax_cache")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO)
    code = ("from deepvision_tpu.startup import compile_cache_dir; "
            "print(compile_cache_dir())")
    there = [subprocess.run([sys.executable, "-c", code], env=env,
                            cwd=cwd, capture_output=True, text=True,
                            check=True).stdout.strip()
             for cwd in (str(REPO), str(REPO / "tests"))]
    assert there == [here, here]  # two processes, two working dirs


# ------------------------------------------------ one process per chip


def test_chip_env_confines_slot_i_to_chip_i():
    envs = [startup.chip_env(i, 4) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               for e in envs)
    with pytest.raises(ValueError, match="one process at a time"):
        startup.chip_env(4, 4)


def test_fleet_larger_than_the_host_is_refused_at_start():
    with pytest.raises(ValueError, match="has 4"):
        process_replica_factory(lambda sid: ["true"], replicas=5,
                                devices={**V5E, "count": 4})
    with pytest.raises(ValueError, match="has 1"):  # --fleet 2, one chip
        process_replica_factory(lambda sid: ["true"], replicas=2,
                                devices={**V5E, "count": 1})


def test_fleet_factory_gives_each_live_replica_its_own_chip():
    factory = process_replica_factory(
        lambda sid: [sys.executable, "-c", "pass"], replicas=2,
        devices={**V5E, "count": 2})
    r1, r2 = factory("r1"), factory("r2")
    assert [r._env["TPU_VISIBLE_CHIPS"] for r in (r1, r2)] == ["0", "1"]
    with pytest.raises(ReplicaDeadError, match="held by live replicas"):
        factory("r3")
    # r1's process comes and goes: its chip is free for the respawn
    with pytest.raises(ReplicaDeadError):
        r1.start()  # exits before it ever writes a port file
    assert r1.exited and not r2.exited
    assert factory("r3")._env["TPU_VISIBLE_CHIPS"] == "0"


def test_replicas_booting_at_once_never_share_a_chip(monkeypatch):
    """The router boots its replicas on threads: a replica that is
    created but has not spawned its child yet still holds its chip (the
    four-chip run of PR 21 put three replicas on chip 0 through exactly
    this window)."""
    import threading

    from deepvision_tpu.serve import replica as replica_mod

    real_popen = subprocess.Popen

    def slow_popen(*a, **kw):
        time.sleep(0.3)  # widen the window between factory() and spawn
        return real_popen(*a, **kw)

    monkeypatch.setattr(replica_mod.subprocess, "Popen", slow_popen)
    factory = process_replica_factory(
        lambda sid: [sys.executable, "-c", "import time; time.sleep(1)"],
        replicas=4, devices={**V5E, "count": 4})
    chips = []

    def boot(sid):
        r = factory(sid)
        chips.append(r._env["TPU_VISIBLE_CHIPS"])
        with pytest.raises(ReplicaDeadError):
            r.start()  # the child exits without serving

    threads = [threading.Thread(target=boot, args=(f"r{i}",))
               for i in range(4)]
    for t in threads:
        t.start()
        time.sleep(0.1)  # each factory() call lands inside a spawn
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert sorted(chips) == ["0", "1", "2", "3"]


def test_fleet_off_tpu_inherits_the_environment():
    factory = process_replica_factory(
        lambda sid: ["true"], replicas=8,
        devices={"platform": "cpu", "kind": "cpu", "count": 1})
    assert factory("r1")._env is None


def test_supervisor_refuses_local_workers_on_a_tpu(monkeypatch):
    import train_dist

    monkeypatch.setattr(startup, "probe_devices",
                        lambda: {**V5E, "count": 4})
    args = train_dist.build_parser().parse_args(["--supervise", "2"])
    with pytest.raises(SystemExit, match="one process at a time"):
        train_dist.run_supervisor(args, ["-m", "lenet5"])


# ------------------------------------------------- peaks and dispatch


def test_unknown_device_kind_has_no_peaks():
    from tools.hbm_budget import device_peaks

    assert device_peaks("TPU v5 lite") == (197e12, 819.0)
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks("cpu")


def test_lrn_dispatch_says_what_it_chose_and_why():
    assert select_lrn_impl("tpu", 1)[0] == "pallas"
    impl, why = select_lrn_impl("tpu", 4)
    assert impl == "jnp" and "4 devices" in why
    impl, why = select_lrn_impl("cpu", 1)
    assert impl == "jnp" and "cpu" in why
