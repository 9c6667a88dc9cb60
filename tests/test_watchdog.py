"""Stall watchdog (SURVEY §5.3 failure detection): silent device hangs
— a step loop blocked in a C call on a wedged runtime RPC — become loud
warnings or a retryable exit 75."""

import time

import numpy as np

from deepvision_tpu.train.trainer import StallWatchdog


def test_watchdog_fires_on_missing_heartbeat(capsys):
    exits = []
    wd = StallWatchdog(0.3, abort=False, _exit=exits.append).start()
    try:
        wd.beat()  # arm (cold-start compile immunity: unarmed until now)
        time.sleep(1.0)  # then no beats
        assert wd.fired
        assert exits == []  # warn-only mode never exits
        out = capsys.readouterr().out
        assert "[stall]" in out and "--stall-abort" in out
    finally:
        wd.stop()


def test_watchdog_stays_quiet_with_heartbeats(capsys):
    wd = StallWatchdog(0.5, abort=False).start()
    try:
        for _ in range(10):
            time.sleep(0.1)
            wd.beat()
        assert not wd.fired
        assert "[stall]" not in capsys.readouterr().out
    finally:
        wd.stop()


def test_watchdog_abort_calls_exit_75():
    exits = []
    wd = StallWatchdog(0.3, abort=True, _exit=exits.append).start()
    try:
        wd.beat()  # arm
        deadline = time.time() + 5
        while not exits and time.time() < deadline:
            time.sleep(0.05)
        assert exits == [75]
    finally:
        wd.stop()


def test_trainer_heartbeats_keep_watchdog_quiet(tmp_path, mesh8):
    """A real (fast) training run under a tight timeout: per-step and
    per-val-batch beats keep the watchdog from firing."""
    from deepvision_tpu.data.mnist import batches, synthetic_mnist
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.trainer import Trainer

    imgs, labels = synthetic_mnist(64)
    cfg = {
        "name": "lenet5", "batch_size": 16, "input_size": 32,
        "channels": 1, "num_classes": 10, "dataset": "mnist",
        "optimizer": "adam", "optimizer_params": {"lr": 1e-3},
        "total_epochs": 1,
    }
    t = Trainer(
        get_model("lenet5", num_classes=10), cfg, mesh8,
        lambda e: batches(imgs, labels, 16,
                          rng=np.random.default_rng(e)),
        lambda: batches(imgs, labels, 16, drop_remainder=False),
        workdir=tmp_path, steps_per_epoch=4, log_every=0,
        stall_timeout=120.0,
    )
    t.fit(1)
    assert not t._watchdog.fired
    assert not t._watchdog._thread.is_alive()  # stopped by fit()
    t.ckpt.close()


def test_watchdog_not_armed_until_first_beat(capsys):
    """Cold-start immunity: the first step's multi-minute XLA compile
    must not trip the watchdog — it arms on the first heartbeat."""
    wd = StallWatchdog(0.3, abort=False).start()
    try:
        time.sleep(0.8)  # longer than the timeout, but never beaten
        assert not wd.fired
        wd.beat()
        time.sleep(0.8)  # now armed: a missing beat fires
        assert wd.fired
    finally:
        wd.stop()


def test_watchdog_restartable_after_stop():
    """fit() may run repeatedly on one Trainer: start/stop/start works."""
    wd = StallWatchdog(60.0)
    wd.start()
    wd.stop()
    wd.start()
    assert wd._thread.is_alive()
    wd.stop()
    assert not wd._thread.is_alive()


def test_watchdog_fired_resets_on_restart():
    """A non-abort stall in one run must not label every later run on
    the same Trainer as fired: start() clears the fired state."""
    wd = StallWatchdog(0.3, abort=False).start()
    try:
        wd.beat()
        time.sleep(0.8)
        assert wd.fired
    finally:
        wd.stop()
    wd.start()  # second fit() on the same Trainer
    try:
        assert not wd.fired  # stale fired state cleared
        wd.beat()
        assert not wd.fired
    finally:
        wd.stop()


def test_gan_loop_beats_watchdog(tmp_path, mesh8):
    """fit_gan drives the same watchdog contract (start/beat/stop)."""
    from deepvision_tpu.data.mnist import synthetic_mnist
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.gan import (
        create_dcgan_state,
        dcgan_train_step,
        fit_gan,
    )
    from deepvision_tpu.train.trainer import StallWatchdog as WD

    imgs, _ = synthetic_mnist(64)
    imgs28 = ((imgs[:, 2:30, 2:30, :] * 2) - 1).astype(np.float32)

    def data(epoch):
        for s in range(0, 64, 16):
            yield {"image": imgs28[s:s + 16]}

    state = create_dcgan_state(
        get_model("dcgan_generator"), get_model("dcgan_discriminator"))
    wd = WD(120.0)
    fit_gan(state, dcgan_train_step, data, mesh8, epochs=1,
            workdir=str(tmp_path), log_every=0, watchdog=wd)
    assert not wd.fired
    assert not wd._thread.is_alive()  # stopped by fit_gan
