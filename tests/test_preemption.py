"""Preemption-safe training (SURVEY §5.3 — the reference has no
preemption handling; its crash-survival story is `nohup` + logs).

Two layers:

1. in-process: `request_preempt()` mid-epoch saves a synchronous
   checkpoint to ``ckpt_preempt/`` and resume continues BIT-IDENTICALLY
   to the uninterrupted run (epoch-seeded data order + replayed PRNG
   split chain);
2. subprocess: a real ``train.py`` run receives SIGTERM, exits 143 with
   the preemption marker, and ``--resume`` finishes the run from the
   mid-epoch point.
"""

import os
import re
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]

CFG = {
    "name": "lenet5", "batch_size": 16, "input_size": 32,
    "channels": 1, "num_classes": 10, "dataset": "mnist",
    "optimizer": "adam", "optimizer_params": {"lr": 1e-3},
    "total_epochs": 2,
}


def _make_trainer(workdir, mesh8, imgs, labels, preempt_after=None,
                  **trainer_kw):
    from deepvision_tpu.data.mnist import batches
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.trainer import Trainer

    holder = {}

    def train_data(epoch):
        for j, b in enumerate(batches(imgs, labels, 16,
                                      rng=np.random.default_rng(epoch))):
            # fires the flag the way a signal would, but at a
            # deterministic batch position (prefetch runs this generator
            # slightly ahead of the step loop; determinism of the SAVE
            # POINT is not required — only bit-exactness of the resume)
            if preempt_after is not None and j == preempt_after:
                holder["t"].request_preempt()
            yield b

    t = Trainer(
        get_model("lenet5", num_classes=10), CFG, mesh8,
        train_data,
        lambda: batches(imgs, labels, 16, drop_remainder=False),
        workdir=workdir, steps_per_epoch=4, log_every=0,
        **trainer_kw,
    )
    holder["t"] = t
    return t


def test_preempt_resume_is_bit_identical(tmp_path, mesh8):
    """2 epochs straight vs preempt-mid-epoch-0 + resume: the final
    epoch-1 metrics AND parameters must match exactly."""
    import jax

    from deepvision_tpu.data.mnist import synthetic_mnist

    imgs, labels = synthetic_mnist(64)

    t_straight = _make_trainer(tmp_path / "a", mesh8, imgs, labels)
    t_straight.fit(2)
    want = {
        k: t_straight.loggers.data[k]["value"][-1]
        for k in ("train_loss", "val_loss", "val_top1")
    }
    want_params = jax.tree.map(np.asarray, t_straight.state.params)
    t_straight.ckpt.close()

    t1 = _make_trainer(tmp_path / "b", mesh8, imgs, labels,
                       preempt_after=2)
    t1.fit(2)
    assert t1.preempted
    assert (tmp_path / "b" / "lenet5" / "ckpt_preempt").exists()
    t1.ckpt.close()

    t2 = _make_trainer(tmp_path / "b", mesh8, imgs, labels)
    t2.resume()
    assert t2.start_epoch == 0 and t2.start_step > 0  # mid-epoch point
    t2.fit(2)
    assert not t2.preempted
    # the completed epoch save supersedes the preemption checkpoint
    assert not (tmp_path / "b" / "lenet5" / "ckpt_preempt").exists()
    got = {
        k: t2.loggers.data[k]["value"][-1]
        for k in ("train_loss", "val_loss", "val_top1")
    }
    got_params = jax.tree.map(np.asarray, t2.state.params)
    t2.ckpt.close()

    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-6), k
    flat_w, flat_g = (jax.tree.leaves(p) for p in (want_params, got_params))
    for w, g in zip(flat_w, flat_g):
        np.testing.assert_array_equal(w, g)


def test_rss_limit_self_preempts(tmp_path, mesh8, monkeypatch):
    """Crossing --rss-limit-gb must route into the normal preemption
    path: mid-epoch save to ckpt_preempt/, .preempted set (the train.py
    CLI then exits 143 for a supervised --resume relaunch) — a run
    that outgrows host memory otherwise dies in an OOM SIGKILL with no
    save.
    DVTPU_FAKE_RSS trips the in-loop check deterministically; the
    ctor-time storm guard ignores the fake (honor_fake=False) so
    construction with a sane limit still succeeds."""
    from deepvision_tpu.data.mnist import synthetic_mnist

    imgs, labels = synthetic_mnist(64)
    monkeypatch.setenv("DVTPU_FAKE_RSS", str(10**15))  # 1000 TB
    t = _make_trainer(tmp_path / "rss", mesh8, imgs, labels,
                      rss_limit_gb=1000.0)
    t.fit(2)
    assert t.preempted and t._rss_preempted
    assert (tmp_path / "rss" / "lenet5" / "ckpt_preempt").exists()
    t.ckpt.close()

    # resume path is the standard one: picks up the mid-epoch point
    monkeypatch.delenv("DVTPU_FAKE_RSS")
    t2 = _make_trainer(tmp_path / "rss", mesh8, imgs, labels)
    t2.resume()
    assert t2.start_epoch == 0 and t2.start_step > 0
    t2.ckpt.close()


def test_rss_limit_below_baseline_rejected(tmp_path, mesh8):
    """A limit at/below the process's current RSS would re-preempt on
    batch 0 of every relaunch (one batch of progress per full XLA
    recompile) — the ctor must reject it with the numbers the operator
    needs, not start the storm."""
    from deepvision_tpu.data.mnist import synthetic_mnist

    imgs, labels = synthetic_mnist(64)
    with pytest.raises(ValueError, match="at/below the current"):
        _make_trainer(tmp_path / "low", mesh8, imgs, labels,
                      rss_limit_gb=1e-6)


def test_preempt_during_validate_stops_after_epoch(tmp_path, mesh8):
    """A signal landing between train_epoch and the epoch save commits
    the full epoch and stops WITHOUT a preemption checkpoint."""
    from deepvision_tpu.data.mnist import synthetic_mnist

    imgs, labels = synthetic_mnist(64)
    t = _make_trainer(tmp_path / "c", mesh8, imgs, labels)
    orig_validate = t.validate
    calls = []

    def validate_and_preempt():
        out = orig_validate()
        calls.append(1)
        if len(calls) == 2:  # the post-epoch-0 validate (1st is pre-train)
            t.request_preempt()
        return out

    t.validate = validate_and_preempt
    t.fit(2)
    assert t.preempted
    assert not (tmp_path / "c" / "lenet5" / "ckpt_preempt").exists()
    assert t.ckpt.latest_epoch() == 0  # only epoch 0 ran
    t.ckpt.close()


def test_resume_waits_for_inflight_preempt_save(tmp_path, mesh8,
                                                monkeypatch):
    """The r4 field crash (logs/gate_yolo_r4c.log:866-910): a concurrent
    --resume process raced the dying process's in-flight preemption
    save. Under the PreemptLock the resumer must WAIT for the save and
    then pick it up mid-epoch — not crash either process."""
    import threading

    from deepvision_tpu.data.mnist import synthetic_mnist
    from deepvision_tpu.train.trainer import PreemptLock

    imgs, labels = synthetic_mnist(64)
    # widen the locked critical section so the resumer reliably arrives
    # while the save is in flight
    monkeypatch.setenv("DVTPU_PREEMPT_SAVE_DELAY", "4.0")

    t1 = _make_trainer(tmp_path / "d", mesh8, imgs, labels,
                       preempt_after=2)
    # build the resumer BEFORE the save starts: its construction cost
    # must not eat the save-delay window the race depends on
    t2 = _make_trainer(tmp_path / "d", mesh8, imgs, labels)
    errors = []

    def run_a():
        try:
            t1.fit(2)
        except Exception as e:  # the field crash surfaced here
            errors.append(e)

    a = threading.Thread(target=run_a)
    a.start()
    # wait until the dying "process" actually holds the lock
    probe = PreemptLock(tmp_path / "d" / "lenet5" / "ckpt_preempt.lock")
    deadline = time.time() + 120
    while time.time() < deadline:
        if probe.acquire(timeout=0.01):
            probe.release()
            time.sleep(0.05)
        else:
            break  # held by the saver
    else:
        pytest.fail("saver never acquired the preemption lock")

    # concurrent resumer: must block on the lock, then restore the
    # mid-epoch checkpoint the saver was still writing. Re-check the
    # lock is STILL held right before resuming — otherwise the test
    # can pass without exercising the wait path at all.
    assert not probe.acquire(timeout=0.01), (
        "save window closed before resume; race not exercised")
    t2.resume()
    a.join(timeout=120)
    assert not errors, errors  # the dying process's save must not crash
    assert t1.preempted
    assert t2.start_epoch == 0 and t2.start_step > 0  # picked up the save
    t1.ckpt.close()
    t2.ckpt.close()


def test_resume_timeout_never_deletes_inflight_tmp(tmp_path, mesh8):
    """While a (possibly wedged) writer holds the PreemptLock, resume()
    must leave ckpt_preempt/ untouched — the stale-clear rmtree deleting
    an in-flight *.orbax-checkpoint-tmp dir was the exact r4 failure —
    and fall back to the latest epoch checkpoint. Once the lock is
    free, a genuinely stale preemption dir is still cleared."""
    from deepvision_tpu.data.mnist import synthetic_mnist
    from deepvision_tpu.train.trainer import PreemptLock

    imgs, labels = synthetic_mnist(64)
    t1 = _make_trainer(tmp_path / "e", mesh8, imgs, labels)
    t1.fit(1)  # epoch-0 checkpoint to fall back to
    t1.ckpt.close()

    run = tmp_path / "e" / "lenet5"
    tmp_ckpt = run / "ckpt_preempt" / "5.orbax-checkpoint-tmp"
    tmp_ckpt.mkdir(parents=True)
    (tmp_ckpt / "payload").write_text("in-flight")

    holder = PreemptLock(run / "ckpt_preempt.lock")
    assert holder.acquire(timeout=1.0)
    try:
        t2 = _make_trainer(tmp_path / "e", mesh8, imgs, labels)
        t2.preempt_lock_timeout = 0.3
        t2.resume()  # old code: rmtree'd the tmp dir here
        assert t2.start_epoch == 1 and t2.start_step == 0
        assert (tmp_ckpt / "payload").exists(), (
            "resume deleted another process's in-flight staging dir")
        t2.ckpt.close()
    finally:
        holder.release()

    # lock free + tmp dir older than the epoch checkpoint = stale:
    # the normal cleanup path must still collect it
    t3 = _make_trainer(tmp_path / "e", mesh8, imgs, labels)
    t3.resume()
    assert t3.start_epoch == 1
    assert not (run / "ckpt_preempt").exists()
    t3.ckpt.close()


def test_composed_resilience_zero1_echo_preempt_resume(tmp_path, mesh8):
    """The resilience features COMPOSED (VERDICT r4 weak #6): ZeRO-1
    sharded weight update + data echoing x2 + mid-epoch SIGTERM +
    resume must still be bit-identical to the uninterrupted run with
    the same flags — exactly the configuration a real preempted pod
    run would be in."""
    import jax

    from deepvision_tpu.data.mnist import synthetic_mnist

    imgs, labels = synthetic_mnist(64)
    kw = dict(shard_weight_update=True, data_echo=2)

    t_straight = _make_trainer(tmp_path / "a", mesh8, imgs, labels, **kw)
    t_straight.fit(2)
    want = {
        k: t_straight.loggers.data[k]["value"][-1]
        for k in ("train_loss", "val_loss", "val_top1")
    }
    want_params = jax.tree.map(np.asarray, t_straight.state.params)
    t_straight.ckpt.close()

    t1 = _make_trainer(tmp_path / "b", mesh8, imgs, labels,
                       preempt_after=2, **kw)
    t1.fit(2)
    assert t1.preempted
    assert (tmp_path / "b" / "lenet5" / "ckpt_preempt").exists()
    t1.ckpt.close()

    t2 = _make_trainer(tmp_path / "b", mesh8, imgs, labels, **kw)
    t2.resume()
    assert t2.start_epoch == 0 and t2.start_step > 0  # mid-epoch point
    t2.fit(2)
    assert not t2.preempted
    got = {
        k: t2.loggers.data[k]["value"][-1]
        for k in ("train_loss", "val_loss", "val_top1")
    }
    got_params = jax.tree.map(np.asarray, t2.state.params)
    t2.ckpt.close()

    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-6), k
    for w, g in zip(jax.tree.leaves(want_params),
                    jax.tree.leaves(got_params)):
        np.testing.assert_array_equal(w, g)


def test_preempt_resume_echo_mismatch_rejected(tmp_path, mesh8):
    """Resuming a preemption checkpoint under a different --data-echo
    silently diverges from the uninterrupted run, so it must refuse."""
    from deepvision_tpu.data.mnist import synthetic_mnist

    imgs, labels = synthetic_mnist(64)
    t1 = _make_trainer(tmp_path / "c", mesh8, imgs, labels,
                       preempt_after=2, data_echo=2)
    t1.fit(2)
    assert t1.preempted
    t1.ckpt.close()

    t2 = _make_trainer(tmp_path / "c", mesh8, imgs, labels, data_echo=1)
    with pytest.raises(ValueError, match="data-echo"):
        t2.resume()
    t2.ckpt.close()


def test_unlocked_save_escape_hatch(tmp_path, mesh8):
    """A writer whose lock acquisition times out must still save — but
    into ckpt_preempt_unlocked/, never touching the lock holder's
    directory — and a later resume must pick that save up."""
    from deepvision_tpu.data.mnist import synthetic_mnist
    from deepvision_tpu.train.trainer import PreemptLock

    imgs, labels = synthetic_mnist(64)
    run = tmp_path / "f" / "lenet5"
    holder = PreemptLock(run / "ckpt_preempt.lock")
    assert holder.acquire(timeout=1.0)
    try:
        t1 = _make_trainer(tmp_path / "f", mesh8, imgs, labels,
                           preempt_after=2)
        t1.preempt_lock_timeout = 0.3
        t1.fit(2)
        assert t1.preempted
        assert (run / "ckpt_preempt_unlocked").exists()
        assert not (run / "ckpt_preempt").exists()  # holder's dir untouched
        t1.ckpt.close()
    finally:
        holder.release()

    t2 = _make_trainer(tmp_path / "f", mesh8, imgs, labels)
    t2.resume()
    assert t2.start_epoch == 0 and t2.start_step > 0
    t2.ckpt.close()


def test_sigterm_with_concurrent_resume_subprocess(tmp_path):
    """End-to-end replay of the r4 field sequence: SIGTERM a real
    train.py, immediately launch a second process with --resume while
    the first is still saving. The dying process must finish its save
    cleanly (exit 143, no traceback) and the resumer must wait and
    continue from the mid-epoch point."""
    env = dict(os.environ, DVTPU_PREEMPT_SAVE_DELAY="30")
    cmd = [
        sys.executable, "-u", "train.py", "-m", "lenet5",
        "--platform", "cpu", "--synthetic-size", "4096",
        "--batch-size", "32", "--epochs", "2", "--workdir", str(tmp_path),
    ]
    a = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    lines = []
    deadline = time.time() + 300
    for line in a.stdout:
        lines.append(line)
        if re.search(r"\[epoch 0 batch [1-9]", line):
            a.send_signal(signal.SIGTERM)
            break
        assert time.time() < deadline, "".join(lines)
    # launch the resumer NOW — the dying process holds the lock for
    # ~30s, so the resumer's startup lands inside the save window
    b = subprocess.Popen(cmd + ["--resume"], cwd=REPO,
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    rest, _ = a.communicate(timeout=300)
    out_a = "".join(lines) + rest
    assert a.returncode == 143, out_a
    assert "[preempted] saved epoch 0 step" in out_a, out_a
    assert "Traceback" not in out_a, out_a  # the r4 crash signature
    out_b, _ = b.communicate(timeout=600)
    assert b.returncode == 0, out_b
    assert "Traceback" not in out_b, out_b
    m = re.search(r"resumed at epoch 0 step (\d+)", out_b)
    assert m and int(m.group(1)) > 0, out_b
    assert "[epoch 1]" in out_b  # ran to completion


def test_sigterm_subprocess_roundtrip(tmp_path):
    """Real signal path through the shipped CLI: SIGTERM -> marker +
    exit 143 -> --resume continues from the recorded step and finishes."""
    # enough steps (4096*0.9/32 = 115/epoch) that the signal reliably
    # lands mid-epoch-0 after the "batch 10" log line appears
    cmd = [
        sys.executable, "-u", "train.py", "-m", "lenet5",
        "--platform", "cpu", "--synthetic-size", "4096",
        "--batch-size", "32", "--epochs", "2", "--workdir", str(tmp_path),
    ]
    p = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    # wait until training is demonstrably mid-epoch, then preempt
    lines = []
    deadline = time.time() + 300
    for line in p.stdout:
        lines.append(line)
        if re.search(r"\[epoch 0 batch [1-9]", line):
            p.send_signal(signal.SIGTERM)
            break
        assert time.time() < deadline, "".join(lines)
    rest, _ = p.communicate(timeout=300)
    out = "".join(lines) + rest
    assert p.returncode == 143, out
    assert "[preempted] saved epoch 0 step" in out, out

    r = subprocess.run(cmd + ["--resume"], cwd=REPO, timeout=600,
                       stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                       text=True)
    assert r.returncode == 0, r.stdout
    m = re.search(r"resumed at epoch 0 step (\d+)", r.stdout)
    assert m and int(m.group(1)) > 0, r.stdout
    assert "[epoch 1]" in r.stdout  # ran to completion
