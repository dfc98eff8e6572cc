"""What a run on the chip rests on, checked on the CPU rig: where the
compile cache lives, that a process started for a chip refuses any other
device, and that nothing switches platform behind the caller's back."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from tf_yarn_tpu import compile_cache
from tf_yarn_tpu.parallel import mesh as mesh_lib

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _export_from(cwd, env):
    """compile_cache.export() as a fresh interpreter started in `cwd`
    computes it (no JAX involved)."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "from tf_yarn_tpu import compile_cache; "
         "print(compile_cache.export())"],
        capture_output=True, text=True, cwd=cwd, env=env, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip()


def test_cache_dir_from_the_environment_is_used_verbatim(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path / "cc"))
    assert compile_cache.export() == str(tmp_path / "cc")
    assert compile_cache.stats()["dir"] == str(tmp_path / "cc")


def test_cache_dir_defaults_to_one_place_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
    assert compile_cache.export() == os.path.join(_REPO, ".jax_compile_cache")
    # ...and exports it, so that children agree.
    assert os.environ[compile_cache.ENV_CACHE_DIR] == compile_cache.export()


def test_cache_dir_does_not_depend_on_the_working_directory(tmp_path):
    env = dict(os.environ)
    env.pop(compile_cache.ENV_CACHE_DIR, None)
    first, second = tmp_path / "a", tmp_path / "b"
    first.mkdir()
    second.mkdir()
    assert (_export_from(str(first), env) == _export_from(str(second), env)
            == os.path.join(_REPO, ".jax_compile_cache"))


def test_enable_points_jax_at_the_cache_and_counts_hits(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path))
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        cc.reset_cache()
        start = compile_cache.stats()

        def program(x):
            return jnp.tanh(x) @ x.T + 7.25

        jax.jit(program)(jnp.ones((8, 8))).block_until_ready()
        assert compile_cache.stats()["misses"] > start["misses"]
        assert compile_cache.entries() > 0
        jax.clear_caches()  # forget the executable, keep the directory
        jax.jit(program)(jnp.ones((8, 8))).block_until_ready()
        assert compile_cache.stats()["hits"] > start["hits"]
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", before[1])
        cc.reset_cache()


def test_a_process_started_for_a_chip_refuses_the_cpu(monkeypatch):
    monkeypatch.delenv("TPU_YARN_PLATFORM", raising=False)
    with pytest.raises(RuntimeError) as raised:
        mesh_lib.select_devices()
    message = str(raised.value)
    assert "started for a TPU chip" in message and "'cpu'" in message
    assert "TPU_YARN_PLATFORM=cpu" in message


@pytest.mark.parametrize("how", ["env", "argument"])
def test_the_cpu_rig_is_there_when_asked_for_by_name(monkeypatch, how):
    if how == "env":
        monkeypatch.setenv("TPU_YARN_PLATFORM", "cpu")
        devices = mesh_lib.select_devices(8)
    else:
        monkeypatch.delenv("TPU_YARN_PLATFORM", raising=False)
        devices = mesh_lib.select_devices(8, platform="cpu")
    assert [d.platform for d in devices] == ["cpu"] * 8


def test_run_experiment_checks_the_platform_first(monkeypatch):
    from tf_yarn_tpu import experiment

    monkeypatch.delenv("TPU_YARN_PLATFORM", raising=False)
    with pytest.raises(RuntimeError, match="started for a TPU chip"):
        experiment.run_experiment(None, object())  # never looks at it


def test_device_report_names_what_jax_reports():
    report = mesh_lib.device_report()
    assert report["platform"] == "cpu" and report["kind"] == "cpu"
    assert report["count"] == len(jax.devices())
    assert report["visible_chips"] == os.environ.get("TPU_VISIBLE_CHIPS")


def test_graft_entry_no_longer_switches_platform():
    import __graft_entry__

    with pytest.raises(RuntimeError, match="need 64 devices, have 8 x cpu"):
        __graft_entry__.dryrun_multichip(64)


def test_no_version_shims_left_in_the_package():
    hits = subprocess.run(
        ["grep", "-rnE", r"hasattr\(jax|inspect\.signature\(\s*custom_part",
         os.path.join(_REPO, "tf_yarn_tpu")],
        capture_output=True, text=True,
    ).stdout
    assert hits == ""
