"""Slice backends: how task programs get placed onto machines.

The reference delegates placement to YARN through skein services
(reference: client.py:210-263 builds one `skein.Service` per task type and
`submit_and_connect`s). On TPU there is no resource manager in the loop, so
placement is a first-class, pluggable seam:

* :class:`LocalBackend` — every task instance is a subprocess on this host.
  Serves two roles: single-host TPU-VM runs (the common case: one process
  drives all local chips) and the *real-process* integration harness for
  CI (SURVEY.md §4's "fake backend" requirement — no mocks, actual
  processes coordinating through the actual KV service).
* :class:`SshBackend` — one task runner per TPU-VM worker over ssh; the
  multi-host path (the analog of YARN launching containers on many nodes).

A backend receives fully-resolved :class:`ServiceSpec`s (module to run,
instance count, env) and returns a :class:`ClusterHandle` the driver polls —
the analog of the skein application handle.
"""

from __future__ import annotations

import logging
import os
import shlex
import subprocess
import sys
from abc import ABC, abstractmethod
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from tf_yarn_tpu import constants
from tf_yarn_tpu.topologies import TaskKey

_logger = logging.getLogger(__name__)

# Final statuses, mirroring YARN's (reference: client.py:557-599 polls
# `application_report.final_status` in {"succeeded","failed","killed"}).
RUNNING = "RUNNING"
SUCCEEDED = "SUCCEEDED"
FAILED = "FAILED"
KILLED = "KILLED"

# Side-cars don't gate run completion: the reference's evaluator and
# tensorboard self-terminate after the training tasks stop
# (evaluator_task.py:21-35, _tensorboard_task.py:54-58). Serving tasks
# ARE primary: a crashed server fails (and relaunches) the run — and so
# are ranking replicas and the fleet router, the one endpoint every
# client dials.
PRIMARY_TASK_TYPES = (
    "chief", "worker", "serving", "rank", "router", "prefill",
)


@dataclass
class ServiceSpec:
    """One task type's launch recipe (the skein.Service analog)."""

    module: str
    instances: int
    env: Dict[str, str] = field(default_factory=dict)
    nb_proc: int = 1
    pre_script_hook: str = ""
    # Extra files shipped into each task's working directory, name -> local
    # path (the reference's `files` upload, client.py:337-344).
    files: Dict[str, str] = field(default_factory=dict)
    # TPU chips every instance takes on its host (TaskSpec.chips_per_host);
    # 0 is a CPU task.
    chips_per_host: int = 0


class ClusterHandle(ABC):
    """A launched set of task programs the driver can poll / kill."""

    @abstractmethod
    def status(self) -> str:
        """RUNNING until all primary tasks exit, then SUCCEEDED/FAILED."""

    @abstractmethod
    def tasks(self) -> List[TaskKey]:
        ...

    @abstractmethod
    def kill(self) -> None:
        ...

    @abstractmethod
    def logs(self) -> Dict[str, str]:
        """task "type:id" -> log location (file path or URL)."""


class SliceBackend(ABC):
    # Whether tasks run on other machines (drives the coordinator
    # advertise-address choice in client.run_on_tpu). Custom backends
    # should override when they launch locally.
    is_remote = True

    @abstractmethod
    def launch(
        self, services: Dict[str, ServiceSpec], log_dir: str
    ) -> ClusterHandle:
        ...

    def note_lost_tasks(self, tasks: List[str]) -> None:
        """Driver feedback after a failed attempt: these "type:id" tasks
        died without a lifecycle close (SIGKILLed host, heartbeat-silent
        past the watchdog). Backends that map tasks onto real machines
        use it to blacklist the dead machine from the NEXT launch — an
        elastic shrink that re-places a task on the host that just
        vanished would lose it again immediately. Default: no-op
        (LocalBackend's subprocesses share one host)."""


class _LocalHandle(ClusterHandle):
    def __init__(
        self,
        procs: Dict[TaskKey, subprocess.Popen],
        log_files: Dict[TaskKey, str],
    ) -> None:
        self._procs = procs
        self._log_files = log_files
        self._killed = False

    def status(self) -> str:
        primary = [
            (key, proc)
            for key, proc in self._procs.items()
            if key.type in PRIMARY_TASK_TYPES
        ]
        if not primary:  # side-car-only app: gate on everything
            primary = list(self._procs.items())
        if any(proc.poll() is None for _, proc in primary):
            return RUNNING
        if self._killed:
            return KILLED
        if all(proc.returncode == 0 for _, proc in primary):
            return SUCCEEDED
        return FAILED

    def tasks(self) -> List[TaskKey]:
        return list(self._procs)

    def kill(self) -> None:
        self._killed = True
        for proc in self._procs.values():
            if proc.poll() is None:
                proc.terminate()
        for proc in self._procs.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

    def reap_sidecars(self, timeout: float = 90.0) -> None:
        """Stop side-cars that outlive the primaries. The timeout is the
        grace for the evaluator to finish its final checkpoint (it exits on
        its own once training's stop events are in and nothing is pending);
        TB lingers only its configured termination timeout."""
        for key, proc in self._procs.items():
            if key.type in PRIMARY_TASK_TYPES or proc.poll() is not None:
                continue
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.terminate()
                try:
                    proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    proc.kill()

    def logs(self) -> Dict[str, str]:
        return {key.to_kv_str(): path for key, path in self._log_files.items()}

    def pids(self) -> Dict[str, int]:
        """task "type:id" -> process id, e.g. to send one task the
        SIGTERM a TPU VM sends before it preempts (tf_yarn_tpu.preemption)."""
        return {key.to_kv_str(): proc.pid for key, proc in self._procs.items()}


def local_chip_count() -> int:
    """TPU chips this host has, counted from their device files (one
    `/dev/vfio/<n>` or `/dev/accel<n>` per chip). The launcher must not
    ask JAX: a process that has initialised the TPU backend holds the
    chips, and no child could take them."""
    import glob

    return len(glob.glob("/dev/vfio/[0-9]*")) or len(
        glob.glob("/dev/accel[0-9]*")
    )


def chip_env(first_chip: int, n_chips: int, host_chips: int) -> Dict[str, str]:
    """The libtpu variables that give one process chips
    ``first_chip .. first_chip + n_chips - 1`` of this host and hide the
    rest, so that several chip tasks can share the host. Established on
    a four-chip (2x2) v5e host with libtpu 0.0.34 (docs/Operations.md
    "Several chip tasks on one host"): visibility alone is refused — the
    processes meet on libtpu's lock file — unless each process is also
    told it is a whole slice of that many chips. A task that takes the
    whole host needs none of the variables."""
    if n_chips == host_chips:
        return {}
    bounds = {1: "1,1,1", 2: "1,2,1"}.get(n_chips)
    if bounds is None:
        raise ValueError(
            f"a task can take 1, 2 or all {host_chips} chips of this host, "
            f"not {n_chips}: libtpu gives a process a rectangle of chips"
        )
    return {
        "TPU_VISIBLE_CHIPS": ",".join(
            str(chip) for chip in range(first_chip, first_chip + n_chips)
        ),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


class LocalBackend(SliceBackend):
    """Run every task instance as a local subprocess.

    The per-task command is ``python -m <module>`` with identity/coordinator
    env vars — the same contract `_env.gen_task_module` defines for every
    backend (reference container command: _env.py:10-24).

    One process for each chip: a task whose spec reserves chips
    (``chips_per_host`` > 0) is given exactly those, assigned in task
    order, and a topology that wants more than the host has is refused
    before anything starts. A task that reserves none runs on the CPU
    (``TPU_YARN_PLATFORM=cpu``). Setting ``TPU_YARN_PLATFORM=cpu`` for a
    chip task — the CPU test rig does — runs it on virtual CPU devices
    and takes no chip.
    """

    is_remote = False

    def __init__(self, python: Optional[str] = None) -> None:
        self._python = python or sys.executable

    def _assign_chips(
        self, services: Dict[str, ServiceSpec]
    ) -> Dict[TaskKey, Dict[str, str]]:
        """Platform and chip variables per task instance."""
        wanted: List[Tuple[TaskKey, int]] = []
        out: Dict[TaskKey, Dict[str, str]] = {}
        for task_type, spec in services.items():
            platform = spec.env.get(
                "TPU_YARN_PLATFORM", os.environ.get("TPU_YARN_PLATFORM")
            )
            for task_id in range(spec.instances):
                key = TaskKey(task_type, task_id)
                if not spec.chips_per_host:
                    out[key] = {
                        "TPU_YARN_PLATFORM": "cpu", "JAX_PLATFORMS": "cpu",
                    }
                elif platform != "cpu":
                    wanted.append((key, spec.chips_per_host))
        if not wanted:
            return out
        host_chips = local_chip_count()
        next_chip = 0
        for key, n_chips in wanted:
            # A pair starts on an even chip: "0,1" and "2,3" are the
            # rectangles that were tried.
            first = -(-next_chip // n_chips) * n_chips
            next_chip = first + n_chips
            if next_chip > host_chips:
                raise ValueError(
                    f"this topology asks one host for more TPU chips than "
                    f"its {host_chips} "
                    f"({', '.join(f'{k.to_kv_str()}={n}' for k, n in wanted)})"
                    "; LocalBackend runs every task here. Use fewer or "
                    "smaller chip tasks, SshBackend over more hosts, or "
                    "TPU_YARN_PLATFORM=cpu for the virtual-device rig"
                )
            out[key] = chip_env(first, n_chips, host_chips)
        return out

    def launch(
        self, services: Dict[str, ServiceSpec], log_dir: str
    ) -> _LocalHandle:
        os.makedirs(log_dir, exist_ok=True)
        chip_envs = self._assign_chips(services)
        procs: Dict[TaskKey, subprocess.Popen] = {}
        log_files: Dict[TaskKey, str] = {}
        for task_type, spec in services.items():
            for task_id in range(spec.instances):
                key = TaskKey(task_type, task_id)
                env = dict(os.environ)
                env.update(chip_envs.get(key, {}))
                env.update(spec.env)
                env[constants.ENV_TASK_KEY] = key.to_kv_str()
                workdir = None
                if spec.files:
                    # Each task gets a working dir with the shipped files
                    # (container-cwd semantics of the reference's uploads).
                    import shutil

                    workdir = os.path.join(
                        log_dir, f"{task_type}-{task_id}-files"
                    )
                    os.makedirs(workdir, exist_ok=True)
                    ignore = shutil.ignore_patterns(
                        "__pycache__", "*.pyc", ".git", ".pytest_cache",
                        "node_modules",
                    )
                    for name, src in spec.files.items():
                        dst = os.path.join(workdir, name)
                        os.makedirs(os.path.dirname(dst), exist_ok=True)
                        if os.path.isdir(src):
                            shutil.copytree(
                                src, dst, dirs_exist_ok=True, ignore=ignore
                            )
                        else:
                            shutil.copy(src, dst)
                    # cwd moves to the workdir; keep the driver's cwd
                    # importable (python -m relied on it for source
                    # checkouts where the package isn't installed).
                    env["PYTHONPATH"] = (
                        os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
                    )
                log_path = os.path.join(log_dir, f"{task_type}-{task_id}.log")
                log_files[key] = log_path
                log_file = open(log_path, "wb")
                cmd = [self._python, "-m", spec.module]
                if spec.pre_script_hook:
                    shell = f"{spec.pre_script_hook}; exec {shlex.join(cmd)}"
                    procs[key] = subprocess.Popen(
                        ["/bin/sh", "-c", shell],
                        env=env,
                        cwd=workdir,
                        stdout=log_file,
                        stderr=subprocess.STDOUT,
                    )
                else:
                    procs[key] = subprocess.Popen(
                        cmd,
                        env=env,
                        cwd=workdir,
                        stdout=log_file,
                        stderr=subprocess.STDOUT,
                    )
                log_file.close()
                _logger.info("launched %s as pid %d", key, procs[key].pid)
        return _LocalHandle(procs, log_files)


@dataclass
class TpuVmHost:
    """One TPU VM worker reachable over ssh."""

    hostname: str
    worker_index: int


class SshBackend(SliceBackend):
    """Place one task runner per TPU-VM worker over ssh.

    The multi-host analog of YARN container launch: host *i* of the slice
    runs the *i*-th task instance (chief = worker 0, SURVEY.md §7.2).

    * ``hosts=None`` autodiscovers the slice's workers
      (tf_yarn_tpu.discovery: env override → GCE metadata → gcloud).
    * ``files=`` on a ServiceSpec are shipped per task: tarred locally,
      streamed over the ssh channel into a per-run remote workdir, and the
      task starts with cwd there and the workdir on PYTHONPATH — container
      upload semantics (reference: client.py:337-344) without needing a
      shared filesystem. `remote_prefix` (a pre-provisioned code root)
      additionally lands on PYTHONPATH.
    * ``ssh_cmd`` swaps the transport binary — integration tests drive the
      full path through a local shell shim, no sshd required.
    """

    is_remote = True

    def __init__(
        self,
        hosts: Optional[List[TpuVmHost]] = None,
        python: str = "python3",
        remote_prefix: str = "",
        ssh_options: Optional[List[str]] = None,
        ssh_cmd: Optional[List[str]] = None,
        tpu_name: Optional[str] = None,
        zone: Optional[str] = None,
    ) -> None:
        self._hosts = hosts
        self._python = python
        self._remote_prefix = remote_prefix
        self._ssh_cmd = list(ssh_cmd) if ssh_cmd else [
            "ssh", *(ssh_options or ["-o", "StrictHostKeyChecking=no"])
        ]
        self._tpu_name = tpu_name
        self._zone = zone
        # Dead-host blacklist (docs/Resilience.md "Elastic training"):
        # task "type:id" -> hostname from the LAST launch, and the
        # hostnames the driver reported lost. A blacklisted host is
        # excluded from every later launch's placement, so an elastic
        # shrink relaunches on the survivors instead of re-placing a
        # task on the machine that just went silent.
        self._last_assignment: Dict[str, str] = {}
        self._dead_hosts: set = set()

    def note_lost_tasks(self, tasks: List[str]) -> None:
        for task in tasks:
            hostname = self._last_assignment.get(task)
            if hostname is None:
                continue
            if hostname not in self._dead_hosts:
                _logger.warning(
                    "blacklisting host %s (ran %s, reported lost); it is "
                    "excluded from later launches", hostname, task,
                )
            self._dead_hosts.add(hostname)

    @property
    def dead_hosts(self) -> List[str]:
        """The blacklisted hostnames, for introspection/tests."""
        return sorted(self._dead_hosts)

    def _resolve_hosts(self) -> List[TpuVmHost]:
        if self._hosts is None:
            from tf_yarn_tpu.discovery import discover_tpu_vm_hosts

            self._hosts = discover_tpu_vm_hosts(self._tpu_name, self._zone)
        live = [
            host for host in self._hosts
            if host.hostname not in self._dead_hosts
        ]
        if self._dead_hosts and not live:
            raise RuntimeError(
                f"every known host is blacklisted as dead "
                f"({sorted(self._dead_hosts)}); refusing to launch"
            )
        return live

    @staticmethod
    def _pack_files(files: Dict[str, str]) -> str:
        """Tar `name -> local path` entries into a temp archive. Cache and
        VCS trees are pruned (the env-shipping default includes whole
        package dirs; __pycache__/.git must not ride to every VM)."""
        import tarfile
        import tempfile

        skip = {"__pycache__", ".git", ".pytest_cache", "node_modules"}

        def _filter(info):
            parts = info.name.split("/")
            if any(p in skip for p in parts) or info.name.endswith(".pyc"):
                return None
            return info

        fd, tar_path = tempfile.mkstemp(suffix=".tar.gz", prefix="tpu_yarn_files-")
        os.close(fd)
        with tarfile.open(tar_path, "w:gz") as tar:
            for name, src in files.items():
                tar.add(src, arcname=name, filter=_filter)
        return tar_path

    def _ship_files(self, hostname: str, tar_path: str, remote_dir: str) -> None:
        """Stream the tar through the ssh channel into remote_dir."""
        unpack = f"mkdir -p {remote_dir} && tar xzf - -C {remote_dir}"
        with open(tar_path, "rb") as tar_file:
            result = subprocess.run(
                [*self._ssh_cmd, hostname, unpack],
                stdin=tar_file,
                capture_output=True,
            )
        if result.returncode != 0:
            raise RuntimeError(
                f"shipping files to {hostname} failed: "
                f"{result.stderr.decode(errors='replace').strip()}"
            )

    @staticmethod
    def _dq_escape(value: str) -> str:
        """Escape for interpolation inside a double-quoted shell string
        (so `$PWD`-style parts we add on purpose still expand)."""
        for ch in ("\\", '"', "$", "`"):
            value = value.replace(ch, "\\" + ch)
        return value

    def launch(
        self, services: Dict[str, ServiceSpec], log_dir: str
    ) -> _LocalHandle:
        import re
        from concurrent.futures import ThreadPoolExecutor

        os.makedirs(log_dir, exist_ok=True)
        hosts = self._resolve_hosts()
        # The run id lands in remote shell commands: keep it shell-inert.
        run_id = re.sub(
            r"[^A-Za-z0-9._-]", "_",
            os.path.basename(os.path.normpath(log_dir)),
        )
        assignments: List[Tuple[TaskKey, ServiceSpec]] = []
        for task_type in ("chief", "worker", "evaluator", "tensorboard"):
            spec = services.get(task_type)
            if spec is None:
                continue
            for task_id in range(spec.instances):
                assignments.append((TaskKey(task_type, task_id), spec))
        if len(assignments) > len(hosts):
            raise ValueError(
                f"{len(assignments)} task instances > {len(hosts)} TPU VM hosts"
            )
        tar_cache: Dict[int, str] = {}
        procs: Dict[TaskKey, subprocess.Popen] = {}
        log_files: Dict[TaskKey, str] = {}
        # Fresh task->host map per launch: note_lost_tasks consults the
        # LAST placement (a relaunch may shuffle tasks across hosts).
        self._last_assignment = {
            key.to_kv_str(): host.hostname
            for host, (key, _spec) in zip(hosts, assignments)
        }
        try:
            # Ship files to every host first, concurrently — launch time
            # stays bounded by the slowest transfer, not the host count.
            remote_dirs: Dict[TaskKey, str] = {}
            ship_jobs = []
            for host, (key, spec) in zip(hosts, assignments):
                if not spec.files:
                    continue
                if id(spec) not in tar_cache:
                    tar_cache[id(spec)] = self._pack_files(spec.files)
                remote_dirs[key] = (
                    f"$HOME/.tpu_yarn_runs/{run_id}/{key.type}-{key.id}"
                )
                ship_jobs.append(
                    (host.hostname, tar_cache[id(spec)], remote_dirs[key])
                )
            if ship_jobs:
                with ThreadPoolExecutor(max_workers=min(16, len(ship_jobs))) as pool:
                    for future in [
                        pool.submit(self._ship_files, *job) for job in ship_jobs
                    ]:
                        future.result()

            for host, (key, spec) in zip(hosts, assignments):
                workdir_prefix = ""
                pythonpath_parts = []
                if self._remote_prefix:
                    pythonpath_parts.append(self._dq_escape(self._remote_prefix))
                if spec.files:
                    workdir_prefix = f"cd {remote_dirs[key]} && "
                    pythonpath_parts.append("$PWD")
                elif self._remote_prefix:
                    workdir_prefix = (
                        f"cd {shlex.quote(self._remote_prefix)} && "
                    )
                task_env = {
                    **spec.env, constants.ENV_TASK_KEY: key.to_kv_str()
                }
                # PYTHONPATH merges (matching LocalBackend) instead of the
                # last `env` assignment silently winning.
                caller_pythonpath = task_env.pop("PYTHONPATH", "")
                if caller_pythonpath:
                    pythonpath_parts.append(self._dq_escape(caller_pythonpath))
                env_exports = " ".join(
                    f"{k}={shlex.quote(v)}" for k, v in task_env.items()
                )
                if pythonpath_parts:
                    # Deliberately double-quoted: $PWD/$PYTHONPATH expand in
                    # the remote shell; literal parts are escaped above.
                    env_exports += (
                        f' PYTHONPATH="{":".join(pythonpath_parts)}:$PYTHONPATH"'
                    )
                hook = f"{spec.pre_script_hook}; " if spec.pre_script_hook else ""
                remote_cmd = (
                    f"{workdir_prefix}{hook}env {env_exports} "
                    f"{self._python} -m {spec.module}"
                )
                log_path = os.path.join(log_dir, f"{key.type}-{key.id}.log")
                log_files[key] = log_path
                with open(log_path, "wb") as log_file:
                    procs[key] = subprocess.Popen(
                        [*self._ssh_cmd, host.hostname, remote_cmd],
                        stdout=log_file,
                        stderr=subprocess.STDOUT,
                    )
                _logger.info("launched %s on %s", key, host.hostname)
        except Exception:
            # Don't leak half a cluster: reap anything already started.
            for key, proc in procs.items():
                if proc.poll() is None:
                    _logger.warning("killing partially-launched %s", key)
                    proc.terminate()
            raise
        finally:
            for tar_path in tar_cache.values():
                try:
                    os.unlink(tar_path)
                except OSError:
                    pass
        return _LocalHandle(procs, log_files)
