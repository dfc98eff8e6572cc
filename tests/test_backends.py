"""Backend tests: LocalBackend status transitions, SshBackend command
construction (no ssh connection needed)."""

import time
from unittest import mock

from tf_yarn_tpu.backends import (
    KILLED,
    RUNNING,
    SUCCEEDED,
    LocalBackend,
    ServiceSpec,
    SshBackend,
    TpuVmHost,
)


def test_local_backend_killed_status(tmp_path):
    backend = LocalBackend()
    handle = backend.launch(
        {"worker": ServiceSpec(module="tf_yarn_tpu.tasks._spin", instances=1)},
        str(tmp_path),
    )
    assert handle.status() == RUNNING
    handle.kill()
    deadline = time.time() + 15
    while handle.status() == RUNNING and time.time() < deadline:
        time.sleep(0.2)
    assert handle.status() == KILLED


def test_local_backend_success_status(tmp_path):
    backend = LocalBackend()
    handle = backend.launch(
        {"worker": ServiceSpec(module="platform", instances=2)},  # exits 0
        str(tmp_path),
    )
    deadline = time.time() + 30
    while handle.status() == RUNNING and time.time() < deadline:
        time.sleep(0.2)
    assert handle.status() == SUCCEEDED
    logs = handle.logs()
    assert set(logs) == {"worker:0", "worker:1"}


def test_ssh_backend_command_construction(tmp_path):
    hosts = [TpuVmHost("tpu-vm-0", 0), TpuVmHost("tpu-vm-1", 1)]
    backend = SshBackend(hosts, remote_prefix="/opt/code")
    captured = []

    def fake_popen(cmd, **kwargs):
        captured.append(cmd)
        proc = mock.Mock()
        proc.poll.return_value = 0
        proc.returncode = 0
        proc.pid = 1234
        return proc

    with mock.patch("subprocess.Popen", side_effect=fake_popen):
        backend.launch(
            {
                "chief": ServiceSpec(
                    module="tf_yarn_tpu.tasks.worker",
                    instances=1,
                    env={"TPU_YARN_COORDINATOR": "10.0.0.1:9999"},
                ),
                "worker": ServiceSpec(
                    module="tf_yarn_tpu.tasks.worker", instances=1, env={}
                ),
            },
            str(tmp_path),
        )
    assert len(captured) == 2
    chief_cmd = captured[0]
    assert chief_cmd[0] == "ssh"
    assert chief_cmd[-2] == "tpu-vm-0"
    remote = chief_cmd[-1]
    assert "cd /opt/code" in remote
    assert "TPU_YARN_TASK=chief:0" in remote
    assert "TPU_YARN_COORDINATOR=10.0.0.1:9999" in remote
    assert "-m tf_yarn_tpu.tasks.worker" in remote
    # chief occupies host 0, worker host 1 (slice ordering).
    assert captured[1][-2] == "tpu-vm-1"
    assert "TPU_YARN_TASK=worker:0" in captured[1][-1]


def test_ssh_backend_too_many_tasks():
    backend = SshBackend([TpuVmHost("h", 0)])
    import pytest

    with pytest.raises(ValueError, match="TPU VM hosts"):
        backend.launch(
            {"worker": ServiceSpec(module="m", instances=2)}, "/tmp"
        )


def _write_fake_ssh(tmp_path, fake_home):
    """A local stand-in for ssh: args are (hostname, remote_cmd); run the
    command in a shell with HOME pinned to the test dir. stdin passes
    through, so tar-over-the-channel file shipping works for real."""
    shim = tmp_path / "fake_ssh"
    shim.write_text(
        "#!/bin/sh\n"
        f'export HOME="{fake_home}"\n'
        'exec /bin/sh -c "$2"\n'
    )
    shim.chmod(0o755)
    return str(shim)


def test_ssh_backend_ships_files_and_runs(tmp_path):
    """Full files= path over the ssh transport (shimmed locally): tar is
    streamed through the channel, unpacked into a per-task workdir, and the
    task starts there with the workdir on PYTHONPATH."""
    import os
    import sys
    import time as time_mod

    payload = tmp_path / "data.txt"
    payload.write_text("hello-from-driver")
    fake_home = tmp_path / "remote_home"
    fake_home.mkdir()
    backend = SshBackend(
        hosts=[TpuVmHost("tpu-vm-0", 0)],
        python=sys.executable,
        remote_prefix=os.getcwd(),
        ssh_cmd=[_write_fake_ssh(tmp_path, fake_home)],
    )
    # `platform` exits immediately; what matters is the shipped workdir.
    handle = backend.launch(
        {
            "worker": ServiceSpec(
                module="tf_yarn_tpu.tasks._spin",
                instances=1,
                env={"TPU_YARN_SPIN_SECS": "0"},
                files={"payload/data.txt": str(payload)},
            )
        },
        str(tmp_path / "logs"),
    )
    deadline = time_mod.time() + 30
    while handle.status() == RUNNING and time_mod.time() < deadline:
        time_mod.sleep(0.2)
    assert handle.status() == SUCCEEDED, open(
        handle.logs()["worker:0"]
    ).read()
    # The tar landed under the fake remote HOME, named by run + task.
    runs_root = fake_home / ".tpu_yarn_runs"
    shipped = list(runs_root.rglob("data.txt"))
    assert len(shipped) == 1
    assert shipped[0].read_text() == "hello-from-driver"
    assert shipped[0].parent.name == "payload"
    assert shipped[0].parent.parent.name == "worker-0"


def test_ssh_backend_blacklists_dead_hosts_on_relaunch(tmp_path):
    """PR-8 follow-on: a host whose task was SIGKILLed / heartbeat-silent
    in the previous attempt (reported via note_lost_tasks) must be
    excluded from the next launch's placement — an elastic shrink that
    re-places a task on the dead machine would lose it again."""
    hosts = [TpuVmHost("tpu-vm-0", 0), TpuVmHost("tpu-vm-1", 1),
             TpuVmHost("tpu-vm-2", 2)]
    backend = SshBackend(hosts)
    captured = []

    def fake_popen(cmd, **kwargs):
        captured.append(cmd)
        proc = mock.Mock()
        proc.poll.return_value = 0
        proc.returncode = 0
        proc.pid = 1234
        return proc

    services = {
        "chief": ServiceSpec(module="m", instances=1),
        "worker": ServiceSpec(module="m", instances=2),
    }
    with mock.patch("subprocess.Popen", side_effect=fake_popen):
        backend.launch(services, str(tmp_path))
    # chief:0 -> vm-0, worker:0 -> vm-1, worker:1 -> vm-2.
    assert [cmd[-2] for cmd in captured] == ["tpu-vm-0", "tpu-vm-1",
                                            "tpu-vm-2"]

    # The driver reports worker:1 lost (its host went silent).
    backend.note_lost_tasks(["worker:1"])
    assert backend.dead_hosts == ["tpu-vm-2"]
    # Unknown tasks (never placed) are ignored, not crashed on.
    backend.note_lost_tasks(["worker:9"])
    assert backend.dead_hosts == ["tpu-vm-2"]

    # Elastic shrink relaunch: 1 worker — placed on the SURVIVORS only.
    captured.clear()
    shrunk = {
        "chief": ServiceSpec(module="m", instances=1),
        "worker": ServiceSpec(module="m", instances=1),
    }
    with mock.patch("subprocess.Popen", side_effect=fake_popen):
        backend.launch(shrunk, str(tmp_path))
    assert [cmd[-2] for cmd in captured] == ["tpu-vm-0", "tpu-vm-1"]

    # The relaunch re-recorded placement: losing worker:0 NOW blames
    # vm-1 (its current host), not a stale first-attempt assignment.
    backend.note_lost_tasks(["worker:0"])
    assert backend.dead_hosts == ["tpu-vm-1", "tpu-vm-2"]

    # Capacity accounting reflects the blacklist: 3 tasks no longer fit.
    import pytest

    with mock.patch("subprocess.Popen", side_effect=fake_popen):
        with pytest.raises(ValueError, match="TPU VM hosts"):
            backend.launch(services, str(tmp_path))


def test_ssh_backend_refuses_launch_with_all_hosts_dead(tmp_path):
    backend = SshBackend([TpuVmHost("tpu-vm-0", 0)])
    captured = []

    def fake_popen(cmd, **kwargs):
        captured.append(cmd)
        proc = mock.Mock()
        proc.poll.return_value = 0
        proc.returncode = 0
        proc.pid = 1
        return proc

    services = {"worker": ServiceSpec(module="m", instances=1)}
    with mock.patch("subprocess.Popen", side_effect=fake_popen):
        backend.launch(services, str(tmp_path))
    backend.note_lost_tasks(["worker:0"])
    import pytest

    with pytest.raises(RuntimeError, match="blacklisted"):
        backend.launch(services, str(tmp_path))


def test_driver_feeds_lost_tasks_to_fake_backend():
    """The client's retry path calls note_lost_tasks with the failed
    attempt's RunFailed.lost_tasks — verified against a fake backend
    (the seam SshBackend implements for real)."""
    from tf_yarn_tpu.backends import SliceBackend
    from tf_yarn_tpu.client import RunFailed, _note_lost_to_backend

    class FakeBackend(SliceBackend):
        is_remote = True

        def __init__(self):
            self.noted = []

        def launch(self, services, log_dir):
            raise NotImplementedError

        def note_lost_tasks(self, tasks):
            self.noted.append(list(tasks))

    backend = FakeBackend()
    _note_lost_to_backend(
        backend, RunFailed("attempt failed", lost_tasks=["worker:1"])
    )
    assert backend.noted == [["worker:1"]]
    # No lost tasks -> the hook is not called at all.
    _note_lost_to_backend(backend, RunFailed("attempt failed"))
    assert backend.noted == [["worker:1"]]
    # Backends without the hook (duck-typed, pre-hook) are tolerated.
    _note_lost_to_backend(
        object(), RunFailed("x", lost_tasks=["worker:0"])
    )
    # A hook that raises must not escalate (placement hygiene never
    # turns a retryable failure fatal).

    class ExplodingBackend(FakeBackend):
        def note_lost_tasks(self, tasks):
            raise RuntimeError("boom")

    _note_lost_to_backend(
        ExplodingBackend(), RunFailed("x", lost_tasks=["worker:0"])
    )


# -- one process for each chip ------------------------------------------------

def _chip_services(**chips):
    """{task_type: (instances, chips_per_host)} -> services."""
    return {
        name: ServiceSpec(module="m", instances=n, chips_per_host=c)
        for name, (n, c) in chips.items()
    }


def _assign(monkeypatch, host_chips, services):
    from tf_yarn_tpu import backends

    monkeypatch.delenv("TPU_YARN_PLATFORM", raising=False)  # a chip host
    monkeypatch.setattr(backends, "local_chip_count", lambda: host_chips)
    assigned = LocalBackend()._assign_chips(services)
    return {key.to_kv_str(): env for key, env in assigned.items()}


def test_chip_tasks_get_disjoint_chips_in_task_order(monkeypatch):
    envs = _assign(monkeypatch, 4, _chip_services(
        serving=(4, 1), router=(1, 0)))
    assert [envs[f"serving:{i}"]["TPU_VISIBLE_CHIPS"] for i in range(4)] \
        == ["0", "1", "2", "3"]
    for i in range(4):
        # Visibility alone is refused by libtpu: each process must also
        # be told it is a whole one-chip slice.
        assert envs[f"serving:{i}"]["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert envs[f"serving:{i}"]["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_a_cpu_task_gets_no_chip(monkeypatch):
    envs = _assign(monkeypatch, 4, _chip_services(
        serving=(1, 1), router=(1, 0)))
    assert envs["router:0"] == {
        "TPU_YARN_PLATFORM": "cpu", "JAX_PLATFORMS": "cpu"}


def test_a_pair_of_chips_starts_on_an_even_chip(monkeypatch):
    envs = _assign(monkeypatch, 4, _chip_services(
        worker=(1, 1), serving=(1, 2)))
    assert envs["worker:0"]["TPU_VISIBLE_CHIPS"] == "0"
    assert envs["serving:0"]["TPU_VISIBLE_CHIPS"] == "2,3"
    assert envs["serving:0"]["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"


def test_a_task_that_takes_the_whole_host_is_left_alone(monkeypatch):
    assert _assign(monkeypatch, 4, _chip_services(worker=(1, 4))) \
        == {"worker:0": {}}
    assert _assign(monkeypatch, 1, _chip_services(worker=(1, 1))) \
        == {"worker:0": {}}


def test_oversubscribing_the_host_is_refused(monkeypatch):
    import pytest

    with pytest.raises(ValueError, match="more TPU chips than its 4"):
        _assign(monkeypatch, 4, _chip_services(serving=(4, 1), prefill=(1, 1)))
    with pytest.raises(ValueError, match="more TPU chips than its 0"):
        _assign(monkeypatch, 0, _chip_services(worker=(1, 1)))
    with pytest.raises(ValueError, match="1, 2 or all 4 chips"):
        _assign(monkeypatch, 4, _chip_services(worker=(1, 3)))


def test_cpu_by_name_takes_no_chip(monkeypatch):
    """The CPU rig: TPU_YARN_PLATFORM=cpu, from the driver's environment
    or the task's own, runs a chip task on virtual devices."""
    from tf_yarn_tpu import backends

    def no_count():
        raise AssertionError("must not look for chips")

    monkeypatch.setattr(backends, "local_chip_count", no_count)
    monkeypatch.setenv("TPU_YARN_PLATFORM", "cpu")
    assert LocalBackend()._assign_chips(_chip_services(worker=(2, 4))) == {}
    monkeypatch.delenv("TPU_YARN_PLATFORM")
    services = _chip_services(worker=(2, 4))
    services["worker"].env["TPU_YARN_PLATFORM"] = "cpu"
    assert LocalBackend()._assign_chips(services) == {}


def test_run_on_tpu_carries_chips_per_host_to_the_backend():
    from tf_yarn_tpu.client import _setup_task_env
    from tf_yarn_tpu.topologies import fleet_topology

    services = _setup_task_env(
        fleet_topology(nb_replicas=3, chips_per_host=1), "h:1", "/tmp/x", 0,
        {}, None, "",
    )
    assert services["serving"].chips_per_host == 1
    assert services["router"].chips_per_host == 0


def test_children_see_their_own_chips_and_one_compile_cache(
    monkeypatch, tmp_path
):
    """Real children: what each task's environment holds when it starts,
    and the compile cache each would use — the same one, whatever its
    working directory."""
    import json
    import os

    from tf_yarn_tpu import backends, compile_cache

    monkeypatch.delenv("TPU_YARN_PLATFORM", raising=False)
    monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
    monkeypatch.setattr(backends, "local_chip_count", lambda: 4)
    (tmp_path / "dump_env.py").write_text(
        "import json, os\n"
        "from tf_yarn_tpu import compile_cache\n"
        "keys = ('TPU_VISIBLE_CHIPS', 'TPU_YARN_PLATFORM', 'JAX_PLATFORMS')\n"
        "out = {k: os.environ.get(k) for k in keys}\n"
        "out['cache'] = compile_cache.export()\n"
        "out['cwd'] = os.getcwd()\n"
        "path = os.path.join(os.environ['DUMP_DIR'],\n"
        "                    os.environ['TPU_YARN_TASK'].replace(':', '-'))\n"
        "json.dump(out, open(path, 'w'))\n"
    )
    (tmp_path / "shipped.txt").write_text("x")  # moves the tasks' cwd
    env = {"DUMP_DIR": str(tmp_path),
           "PYTHONPATH": f"{tmp_path}{os.pathsep}{os.environ['PYTHONPATH']}"}
    handle = LocalBackend().launch({
        "serving": ServiceSpec(
            module="dump_env", instances=2, env=env, chips_per_host=1,
            files={"shipped.txt": str(tmp_path / "shipped.txt")}),
        "router": ServiceSpec(module="dump_env", instances=1, env=env),
    }, str(tmp_path / "logs"))
    deadline = time.time() + 60
    while handle.status() == RUNNING and time.time() < deadline:
        time.sleep(0.1)
    assert handle.status() == SUCCEEDED, handle.logs()
    assert set(handle.pids()) == {"serving:0", "serving:1", "router:0"}
    seen = {name: json.loads((tmp_path / name).read_text())
            for name in ("serving-0", "serving-1", "router-0")}
    assert seen["serving-0"]["TPU_VISIBLE_CHIPS"] == "0"
    assert seen["serving-1"]["TPU_VISIBLE_CHIPS"] == "1"
    assert seen["serving-0"]["TPU_YARN_PLATFORM"] is None  # a chip task
    assert seen["router-0"]["TPU_VISIBLE_CHIPS"] is None
    assert seen["router-0"]["TPU_YARN_PLATFORM"] == "cpu"
    assert seen["router-0"]["JAX_PLATFORMS"] == "cpu"
    assert seen["serving-0"]["cwd"] != seen["router-0"]["cwd"]
    assert {s["cache"] for s in seen.values()} \
        == {compile_cache.CHECKOUT_CACHE_DIR}
