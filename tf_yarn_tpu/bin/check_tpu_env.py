"""`check_tpu_env` — environment diagnostic CLI.

TPU-native analog of the reference's `check_hadoop_env` console script
(reference: tf_yarn/bin/check_hadoop_env.py:97-172, wired in setup.py:66-68):
instead of Hadoop env vars + an HDFS write/read probe + a remote skein app,
we check JAX/TPU visibility, coordination-service round-trip, and a local
end-to-end launch.
"""

from __future__ import annotations

import argparse
import logging
import shutil
import sys
import tempfile

_logger = logging.getLogger(__name__)


def check_jax() -> bool:
    try:
        import os

        import jax

        platform = os.environ.get("TPU_YARN_PLATFORM")
        if platform:
            # Check the platform the tasks will be told to use
            # (parallel/mesh.select_devices reads the same variable).
            jax.config.update("jax_platforms", platform)
        devices = jax.devices()
        print(f"OK   jax {jax.__version__}, backend={jax.default_backend()}, "
              f"devices={[str(d) for d in devices]}")
        return True
    except Exception as exc:
        print(f"FAIL jax devices unavailable: {exc}")
        return False


def check_coordination() -> bool:
    from tf_yarn_tpu.coordination import KVClient
    from tf_yarn_tpu.coordination.server_factory import start_best_server

    try:
        server = start_best_server()
        try:
            client = KVClient(server.endpoint)
            client.put("probe", b"ok")
            assert client.wait("probe", timeout=5.0) == b"ok"
            print(f"OK   coordination service round-trip ({client.ping()} server "
                  f"at {server.endpoint})")
            return True
        finally:
            server.stop()
    except Exception as exc:
        print(f"FAIL coordination service: {exc}")
        return False


def check_env_shipping() -> bool:
    """Round-trip the code-shipping path a remote launch relies on: zip
    the installed package, stage it, and run unpack_cmd in a bare shell
    whose PYTHONPATH starts empty — the import must come from the
    unpacked copy (the reference's check ships a test file to HDFS and
    reads it back; here the shipped artifact IS the code)."""
    import os
    import subprocess

    from tf_yarn_tpu import packaging

    try:
        with tempfile.TemporaryDirectory(prefix="check-env-ship-") as tmp:
            staging = os.path.join(tmp, "staging")
            hook = packaging.ship_env(staging, dest=os.path.join(tmp, "code"))
            probe = (
                f"{hook} && {sys.executable} -c "
                "'import tf_yarn_tpu, sys; print(tf_yarn_tpu.__file__)'"
            )
            result = subprocess.run(
                ["/bin/sh", "-c", probe],
                capture_output=True, text=True, timeout=120,
                env={k: v for k, v in os.environ.items()
                     if k != "PYTHONPATH"},
                cwd=tmp,
            )
            imported = result.stdout.strip()
            assert result.returncode == 0, result.stderr.strip()[-300:]
            assert imported.startswith(tmp), imported
        print("OK   env shipping (zip -> stage -> unpack_cmd -> import "
              "from shipped copy)")
        return True
    except Exception as exc:
        print(f"FAIL env shipping: {exc}")
        return False


def check_wheel_shipping() -> bool:
    """Round-trip the third-party-dep channel (run_on_tpu requirements=):
    hand-build a wheel, resolve it through build_wheelhouse (wheels_dir
    path — no egress needed), and pip install --no-index --target it the
    way a worker does; the import must come from the installed copy."""
    import os
    import subprocess
    import zipfile

    from tf_yarn_tpu import packaging

    try:
        with tempfile.TemporaryDirectory(prefix="check-wheel-ship-") as tmp:
            name, version = "tpuyarnprobe", "0.0"
            info = f"{name}-{version}.dist-info"
            dl = os.path.join(tmp, "dl")
            os.makedirs(dl)
            with zipfile.ZipFile(
                os.path.join(dl, f"{name}-{version}-py3-none-any.whl"), "w"
            ) as zf:
                zf.writestr(f"{name}.py", "PROBE = 'ok'\n")
                zf.writestr(f"{info}/METADATA",
                            f"Metadata-Version: 2.1\nName: {name}\n"
                            f"Version: {version}\n")
                zf.writestr(f"{info}/WHEEL",
                            "Wheel-Version: 1.0\nGenerator: doctor\n"
                            "Root-Is-Purelib: true\nTag: py3-none-any\n")
                zf.writestr(f"{info}/RECORD", "")
            house = packaging.build_wheelhouse(
                requirements=[name], wheels_dir=dl)
            try:
                target = os.path.join(tmp, "pydeps")
                install = subprocess.run(
                    [sys.executable, "-m", "pip", "install", "-q",
                     "--no-index", "--find-links", house, "--target", target,
                     "-r", os.path.join(house, packaging.WHEELHOUSE_MANIFEST)],
                    capture_output=True, text=True, timeout=120,
                )
                assert install.returncode == 0, (
                    f"pip install failed: {install.stderr.strip()[-300:]}")
                result = subprocess.run(
                    [sys.executable, "-c",
                     f"import {name}; print({name}.PROBE)"],
                    capture_output=True, text=True, timeout=60,
                    env={**os.environ, "PYTHONPATH": target},
                )
                assert result.returncode == 0, result.stderr.strip()[-300:]
                assert result.stdout.strip() == "ok", result.stdout
            finally:
                # build_wheelhouse memoizes per process for drivers; a
                # short-lived CLI must not leak the /tmp house.
                shutil.rmtree(os.path.dirname(house), ignore_errors=True)
        print("OK   wheel shipping (wheelhouse -> pip install --no-index "
              "-> import)")
        return True
    except Exception as exc:
        print(f"FAIL wheel shipping: {exc}")
        return False


def check_local_run() -> bool:
    """Launch a real one-task run through the full driver path (the analog
    of the reference's remote 1-container check, check_hadoop_env.py:56-93)."""
    from tf_yarn_tpu.client import run_on_tpu
    from tf_yarn_tpu.topologies import TaskSpec

    import os

    fd, probe_path = tempfile.mkstemp(prefix="check-tpu-env-")
    os.close(fd)

    # The closure must capture only the path STRING: a file object would
    # poison the cloudpickle that ships experiment_fn to the task.
    def experiment_fn():
        def run(params):
            with open(probe_path, "w") as fh:
                fh.write(f"rank={params.rank}")

        return run

    try:
        run_on_tpu(
            experiment_fn,
            {"worker": TaskSpec(instances=1)},
            custom_task_module="tf_yarn_tpu.tasks.distributed",
            name="check_tpu_env",
            poll_every_secs=0.2,
        )
        with open(probe_path) as fh:
            assert fh.read() == "rank=0"
        print("OK   end-to-end local run (driver -> coordination -> task)")
        return True
    except Exception as exc:
        print(f"FAIL end-to-end local run: {exc}")
        return False
    finally:
        try:
            os.unlink(probe_path)
        except OSError:
            pass


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--skip-run", action="store_true", help="skip the end-to-end launch probe"
    )
    args = parser.parse_args()
    logging.basicConfig(level=logging.WARNING)
    ok = (check_jax() & check_coordination() & check_env_shipping()
          & check_wheel_shipping())
    if not args.skip_run:
        ok &= check_local_run()
    print("all checks passed" if ok else "some checks FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
