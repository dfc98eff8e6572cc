"""chip_smoke.py's phases, imported and driven on the CPU rig at
TransformerConfig.tiny width: the same entry points (`run_on_tpu` ->
LocalBackend -> task program -> engine), the same checks, kernels in
interpret mode. What only the chip can show stays with the chip."""

import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    """Train -> checkpoint -> serve (int8 KV through the fused kernel, the
    path with the most kernels on it), once for the module."""
    workdir = str(tmp_path_factory.mktemp("chip_smoke"))
    train = chip_smoke.train_phase(chip_smoke.TINY, workdir, seed=0)
    served = chip_smoke.serve_phase(
        chip_smoke.TINY, workdir, train["model_dir"], 0, "serve_int8_fused",
        kv_cache_dtype="int8", decode_attention="fused",
    )
    return train, served


def test_train_phase_trains_and_checkpoints(smoke):
    train, _served = smoke
    steps = chip_smoke.TINY["steps"]
    assert len(train["losses"]) == steps
    assert train["losses"][-1] < train["losses"][0]
    assert train["compile_s"] > 0
    assert os.path.exists(os.path.join(
        train["model_dir"], f"ckpt-{steps}", "MANIFEST.json"))


def test_serve_phase_answers_every_request_without_a_late_compile(smoke):
    _train, served = smoke
    wanted = [new for _prompt, new in chip_smoke.TINY["requests"]]
    assert [len(stream) for stream in served["streams"]] == wanted
    assert served["compiles"] > 0 and served["compiles_after_warmup"] == 0
    assert served["device"]["platform"] == "cpu"  # named, never assumed
    # The server's own account of the cache it used: the one every
    # process of this checkout computes.
    from tf_yarn_tpu import compile_cache

    assert served["compile_cache"]["dir"] == compile_cache.export()


def test_served_streams_equal_generate_legacy(smoke):
    train, served = smoke
    reference = chip_smoke.child_reference({
        "shape": chip_smoke.TINY, "model_dir": train["model_dir"],
        "bodies": served["bodies"], "kv_cache_dtypes": ["int8"],
    })
    assert chip_smoke.report_matches(
        "test", served["bodies"], served["streams"],
        reference["streams"]["int8"],
    ) == 0


def test_kernel_phase_checks_every_kernel_of_the_two_paths():
    report = chip_smoke.child_kernels({"shape": chip_smoke.TINY, "seed": 0})
    assert {k["kernel"] for k in report["kernels"]} == {
        "flash_forward", "flash_backward", "rmsnorm_forward", "rmsnorm_dx",
        "quantize_int8", "int8_decode_attention",
        "paged_int8_decode_attention", "paged_int8_window_attention",
    }
    assert all(k["ok"] for k in report["kernels"]), report["kernels"]
    assert report["device"]["platform"] == "cpu"


def test_report_matches_counts_the_streams_that_differ(capsys):
    bodies = [{"prompt": [1, 2], "max_new_tokens": 3}] * 2
    assert chip_smoke.report_matches(
        "x", bodies, [[5, 6, 7], [5, 6, 7]], [[5, 6, 7], [5, 9, 7]]) == 1
    out = capsys.readouterr().out
    assert "request 0 (prompt 2, 3 new) matches" in out
    assert "request 1 (prompt 2, 3 new) DIFFERS from token 1" in out


def _run_script(path, **env_changes):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_changes)
    env.pop("TPU_YARN_PLATFORM", None)  # as the driver runs it
    return subprocess.run([sys.executable, path], capture_output=True,
                          text=True, env=env, timeout=300)


def test_the_script_fails_and_says_why_when_the_device_is_not_a_tpu():
    proc = _run_script(os.path.join(_REPO, "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "started for a TPU chip" in proc.stderr
    assert "JAX came up on 'cpu'" in proc.stderr


def test_the_script_alone_is_not_the_program(tmp_path):
    alone = shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    proc = _run_script(alone, PYTHONPATH="")
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "tf_yarn_tpu" in proc.stderr
