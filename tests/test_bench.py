"""The measurement scripts measure the chip or nothing: without a TPU
they fail and say why, and the CPU rig is used only when asked for by
name — then every line says so."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TPU_YARN_PLATFORM", None)  # what a user's shell looks like
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, script), *args],
        capture_output=True, text=True, env=env, timeout=300,
    )


@pytest.mark.parametrize(
    "script,args", [("bench.py", ()), ("benchmarks/run.py", ("decode",))]
)
def test_measurement_without_a_chip_is_an_error(script, args):
    proc = _run(script, *args)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", "no result line without a device"
    assert "started for a TPU chip" in proc.stderr
    assert "TPU_YARN_PLATFORM=cpu" in proc.stderr  # how to ask for the rig


def test_cpu_by_name_labels_every_line():
    proc = _run("benchmarks/run.py", "ici_allreduce", "--cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["tpu"] is False
    assert (line["platform"], line["device_kind"]) == ("cpu", "cpu")
    assert line["n_devices"] >= 1


def test_bench_has_no_fallback_left():
    with open(os.path.join(_REPO, "bench.py")) as fh:
        source = fh.read()
    for gone in ("forcing CPU", "_attempt_unwedge", "_probe_backend_alive",
                 "last_tpu_", "cpu-fallback"):
        assert gone not in source
