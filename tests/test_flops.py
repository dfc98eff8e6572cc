"""MFU accounting: peak-FLOPs table, XLA cost analysis, batch counts,
and the steps/sec hook's resume + MFU reporting."""

import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tf_yarn_tpu.utils import flops as flops_lib


class _FakeDevice:
    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize(
    "kind,expected",
    [
        ("TPU v5 lite", 197e12),
        ("TPU v5p", 459e12),
        ("TPU v5", 459e12),
        ("TPU v4", 275e12),
        ("TPU v6 lite", 918e12),
        ("cpu", None),
        ("NVIDIA H100", None),
    ],
)
def test_peak_flops_table(kind, expected):
    assert flops_lib.peak_flops_per_chip(_FakeDevice(kind)) == expected


def test_peak_flops_unknown_tpu_kind_is_an_error():
    with pytest.raises(ValueError, match="TPU v9"):
        flops_lib.peak_flops_per_chip(_FakeDevice("TPU v9"))


def test_batch_counts():
    lm_batch = {"tokens": jnp.zeros((4, 32), jnp.int32)}
    assert flops_lib.batch_counts(lm_batch) == (4, 128)
    hf_batch = {"input_ids": jnp.zeros((2, 16), jnp.int32)}
    assert flops_lib.batch_counts(hf_batch) == (2, 32)
    img_batch = {"x": jnp.zeros((8, 28, 28)), "y": jnp.zeros((8,), jnp.int32)}
    assert flops_lib.batch_counts(img_batch) == (8, None)
    # Integer *feature* columns are not tokens (hashed criteo clicks).
    feat_batch = {"x": jnp.zeros((16, 39), jnp.int32)}
    assert flops_lib.batch_counts(feat_batch) == (16, None)


def test_hook_ragged_interval_scales_work(monkeypatch):
    from tf_yarn_tpu import training

    logged = {}
    monkeypatch.setattr(
        training.mlflow, "log_metric",
        lambda key, value, step=None: logged.setdefault(key, value),
    )
    hook = training._StepsPerSecondHook(
        None, every=2, samples_per_step=8, tokens_per_step=256,
        flops_per_step=1e9, peak_flops=1e12,
    )
    time.sleep(0.02)
    hook.record_batch(8)
    hook.record_batch(4)  # ragged epoch tail
    hook.after_step(2, {"loss": 1.0})
    # 12 of 16 assumed samples ran: every throughput number scales by 3/4.
    assert logged["samples_per_sec_0"] == pytest.approx(
        logged["steps_per_sec_0"] * 8 * 0.75
    )
    assert logged["tokens_per_sec_0"] == pytest.approx(
        logged["steps_per_sec_0"] * 256 * 0.75
    )
    assert logged["mfu_0"] == pytest.approx(
        1e9 * logged["steps_per_sec_0"] * 0.75 / 1e12
    )


def test_train_loop_survives_ragged_tail_batch():
    from tf_yarn_tpu.experiment import as_core_experiment
    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.parallel.mesh import select_devices
    from tf_yarn_tpu.training import train_and_evaluate

    def input_fn():
        rng = np.random.RandomState(0)
        for size in (16, 16, 8):  # epoch tail is half-sized
            yield {"tokens": rng.randint(0, 64, (size, 32)).astype(np.int32)}

    cfg = transformer.TransformerConfig.tiny()
    exp = transformer.make_experiment(
        cfg, train_steps=3, batch_size=16, seq_len=32, input_fn=input_fn,
    )
    metrics = train_and_evaluate(
        as_core_experiment(exp), devices=select_devices(8, platform="cpu")
    )
    assert np.isfinite(metrics["loss"])


def test_compiled_flops_from_cost_analysis():
    x = jnp.ones((64, 64))
    compiled = jax.jit(lambda a: a @ a).lower(x).compile()
    flops = flops_lib.compiled_flops(compiled)
    assert flops is not None and flops >= 2 * 64 * 64 * 64 * 0.5


def test_mfu_arithmetic():
    assert flops_lib.mfu(1e12, 2.0, 4e12) == pytest.approx(0.5)
    assert flops_lib.mfu(None, 2.0, 4e12) is None
    assert flops_lib.mfu(1e12, 2.0, None) is None


def test_hook_resume_not_inflated(monkeypatch):
    from tf_yarn_tpu import training

    logged = {}
    monkeypatch.setattr(
        training.mlflow, "log_metric",
        lambda key, value, step=None: logged.setdefault(key, value),
    )
    hook = training._StepsPerSecondHook(
        None, every=1, resume_step=1000,
        flops_per_step=1e9, samples_per_step=8, tokens_per_step=256,
        peak_flops=1e12,
    )
    time.sleep(0.05)
    hook.record_batch(8)
    hook.after_step(1001, {"loss": 1.0})
    # One step over ~0.05s: far below the ~20000/s a zero-based _step0
    # would report after resume.
    assert logged["steps_per_sec_0"] < 1000
    assert logged["samples_per_sec_0"] == pytest.approx(
        8 * logged["steps_per_sec_0"]
    )
    assert logged["tokens_per_sec_0"] == pytest.approx(
        256 * logged["steps_per_sec_0"]
    )
    assert logged["mfu_0"] == pytest.approx(
        1e9 * logged["steps_per_sec_0"] / 1e12
    )


def test_measure_throughput_reports_flops():
    import optax

    from tf_yarn_tpu.benchmark import measure_throughput
    from tf_yarn_tpu.models import common, linear
    from tf_yarn_tpu.parallel.mesh import select_devices

    model = linear.HashedLinearClassifier(config=linear.LinearConfig(n_buckets=64))
    batch = {
        "x": np.random.RandomState(0).randint(0, 64, (16, 39)).astype(np.int32),
        "y": np.zeros((16,), np.int32),
    }
    stats = measure_throughput(
        model, common.binary_logistic_loss, optax.sgd(0.1), batch,
        steps=3, devices=select_devices(4, platform="cpu"),
    )
    assert stats["model_flops_per_step_per_chip"] > 0
    # CPU rig: no peak table entry, so no MFU claim.
    assert "mfu" not in stats


def test_kernel_bwd_env_restores_operator_override(monkeypatch):
    """The A/B toggle must restore a pre-set global override (an operator
    benchmarking the whole suite on one backward mode), and remove the
    variable entirely when none was set."""
    import os

    from tf_yarn_tpu.benchmark import kernel_bwd_env

    monkeypatch.delenv("TPU_YARN_NORM_KERNEL_BWD", raising=False)
    with kernel_bwd_env(False):
        assert os.environ["TPU_YARN_NORM_KERNEL_BWD"] == "0"
    assert "TPU_YARN_NORM_KERNEL_BWD" not in os.environ

    monkeypatch.setenv("TPU_YARN_NORM_KERNEL_BWD", "0")
    with kernel_bwd_env(True):
        assert os.environ["TPU_YARN_NORM_KERNEL_BWD"] == "1"
    assert os.environ["TPU_YARN_NORM_KERNEL_BWD"] == "0"

    # Restores even when the body raises (one failed variant must not
    # poison the rest of the sweep).
    try:
        with kernel_bwd_env(True):
            raise RuntimeError("variant failed")
    except RuntimeError:
        pass
    assert os.environ["TPU_YARN_NORM_KERNEL_BWD"] == "0"
