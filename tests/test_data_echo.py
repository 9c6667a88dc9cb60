"""Data echoing (arXiv:1907.05550): N optimizer steps per transferred
batch — the input-bound mitigation for hosts/links slower than the chip
(relay-era figures: the fed path sustained ~345 img/s against a 2600
img/s device rate, so echo directly multiplies delivered step
throughput)."""

import numpy as np
import pytest


def _trainer(tmp_path, mesh8, imgs, labels, **kw):
    from deepvision_tpu.data.mnist import batches
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.trainer import Trainer

    cfg = {
        "name": "lenet5", "batch_size": 16, "input_size": 32,
        "channels": 1, "num_classes": 10, "dataset": "mnist",
        "optimizer": "adam", "optimizer_params": {"lr": 1e-3},
        "total_epochs": 1,
    }
    return Trainer(
        get_model("lenet5", num_classes=10), cfg, mesh8,
        lambda e: batches(imgs, labels, 16,
                          rng=np.random.default_rng(e)),
        lambda: batches(imgs, labels, 16, drop_remainder=False),
        workdir=tmp_path, steps_per_epoch=4, log_every=0, **kw,
    )


def test_echo_multiplies_steps_and_learns(tmp_path, mesh8):
    from deepvision_tpu.data.mnist import synthetic_mnist

    imgs, labels = synthetic_mnist(64)
    t = _trainer(tmp_path / "echo", mesh8, imgs, labels, data_echo=3)
    t.fit(1)
    # 4 transferred batches x echo 3 = 12 optimizer steps
    assert int(t.state.step) == 12
    # echoed epochs are attributable in the logged metrics
    assert t.loggers.data["data_echo"]["value"][-1] == 3.0
    assert t.loggers.data["train_loss"]["value"][-1] < 2.3  # learning
    t.ckpt.close()


def test_echo_default_is_off(tmp_path, mesh8):
    from deepvision_tpu.data.mnist import synthetic_mnist

    imgs, labels = synthetic_mnist(64)
    t = _trainer(tmp_path / "noecho", mesh8, imgs, labels)
    t.fit(1)
    assert int(t.state.step) == 4
    assert "data_echo" not in t.loggers.data
    t.ckpt.close()


def test_echo_preempt_resume_bit_identical(tmp_path, mesh8):
    """Echo interacts with the preemption PRNG replay (data_echo splits
    per transferred batch): straight run vs preempt+resume must still
    produce identical parameters."""
    import jax

    from deepvision_tpu.data.mnist import synthetic_mnist

    imgs, labels = synthetic_mnist(64)

    t_a = _trainer(tmp_path / "a", mesh8, imgs, labels, data_echo=2)
    t_a.fit(1)
    want = jax.tree.map(np.asarray, t_a.state.params)
    t_a.ckpt.close()

    t_b = _trainer(tmp_path / "b", mesh8, imgs, labels, data_echo=2)

    real_train_data = t_b.train_data

    def preempting_data(epoch):
        for j, b in enumerate(real_train_data(epoch)):
            if j == 2:
                t_b.request_preempt()
            yield b

    t_b.train_data = preempting_data
    t_b.fit(1)
    assert t_b.preempted
    t_b.ckpt.close()

    t_c = _trainer(tmp_path / "b", mesh8, imgs, labels, data_echo=2)
    t_c.resume()
    assert t_c.start_step > 0
    t_c.fit(1)
    got = jax.tree.map(np.asarray, t_c.state.params)
    t_c.ckpt.close()

    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(w, g)
