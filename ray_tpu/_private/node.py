"""Node process orchestration.

Analog of ray: python/ray/_private/node.py:37 Node + services.py: starts and
owns the per-node processes (GCS on the head, a raylet per node), discovers
their ports via port files, and tears them down on shutdown. Sessions live
under /dev/shm when available so the object store's files are true shared
memory. Session layout (one dir per cluster session)::

    session_<ts>_<rand>/
      cluster_token            rpc auth token (0600)
      gcs_store.log            GCS persistence log
      logs/                    per-process stdout/stderr
      store_<node_id12>/       raylet object store (per node)
        index.shm              shared-memory object index (slab arena)
        slabs/seg_*.slab       leased slab segments (sparse tmpfs)
        <oid>.obj              one-file objects (spill restores, fallback)

The store dirs are tmpfs-backed shared memory: ``shutdown`` removes this
node's store dir so slab segments and mappings don't outlive the session
in /dev/shm (stale sessions would otherwise pin host memory until a
reboot).
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import tempfile
import time
import uuid
from typing import Callable, Dict, Optional

from ray_tpu._private import steptrace

logger = logging.getLogger(__name__)

DEFAULT_SESSION_ROOT = "/dev/shm/ray_tpu" if os.path.isdir("/dev/shm") else None


def _make_session_dir(session_root: Optional[str] = None) -> str:
    root = session_root or DEFAULT_SESSION_ROOT or os.path.join(
        tempfile.gettempdir(), "ray_tpu"
    )
    session_dir = os.path.join(root, f"session_{time.strftime('%Y%m%d-%H%M%S')}_{uuid.uuid4().hex[:8]}")
    os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)
    return session_dir


def load_cluster_token(session_dir: Optional[str] = None) -> Optional[str]:
    """Load a persisted cluster token into the environment if unset.

    Tries, in order: an explicit ``session_dir/cluster_token``, then the CLI
    state file (~/.ray_tpu/cluster.json) token_file entry. Returns the token
    or None. No-op when RAY_TPU_CLUSTER_TOKEN is already exported.
    """
    if os.environ.get("RAY_TPU_CLUSTER_TOKEN"):
        return os.environ["RAY_TPU_CLUSTER_TOKEN"]
    candidates = []
    if session_dir:
        candidates.append(os.path.join(session_dir, "cluster_token"))
    state_file = os.path.expanduser("~/.ray_tpu/cluster.json")
    try:
        with open(state_file) as f:
            state = json.load(f)
        if state.get("token_file"):
            candidates.append(state["token_file"])
        if state.get("session_dir"):
            candidates.append(os.path.join(state["session_dir"], "cluster_token"))
    except (OSError, ValueError):
        pass
    for path in candidates:
        try:
            with open(path) as f:
                token = f.read().strip()
            if token:
                os.environ["RAY_TPU_CLUSTER_TOKEN"] = token
                return token
        except OSError:
            continue
    return None


def _wait_port_file(path: str, timeout: float = 30.0) -> list:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            with open(path) as f:
                return f.read().strip().split("\n")
        time.sleep(0.05)
    raise TimeoutError(f"process did not write port file {path}")


def package_env(env: Optional[dict] = None) -> dict:
    """Env with PYTHONPATH including ray_tpu's parent dir, so subprocesses can
    import the package regardless of the caller's cwd/installation."""
    env = dict(env if env is not None else os.environ)
    import ray_tpu

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
    existing = env.get("PYTHONPATH", "")
    if pkg_root not in existing.split(os.pathsep):
        env["PYTHONPATH"] = pkg_root + (os.pathsep + existing if existing else "")
    return env


def _spawn(cmd, log_path: str, env: dict) -> subprocess.Popen:
    out = open(log_path, "ab")
    return subprocess.Popen(cmd, stdout=out, stderr=subprocess.STDOUT, env=env)


# How long a signalled process may take to exit before it is killed. Sized
# from the chip: a worker that holds a v5e host's four chips is not gone for
# up to 16 s after it calls exit (the kernel unpins its memory first; PERF.md
# section 7), and a train worker may drain for train_drain_grace_s (30 s)
# before that.
EXIT_GRACE_S = 60.0
# The raylet's own stop may wait two graces for its workers before it exits.
RAYLET_EXIT_GRACE_S = 2 * EXIT_GRACE_S + 10.0
EXIT_POLL_S = 0.02


class ProcessEnd:
    """The end of one child process, the same for a raylet's worker and for a
    node's raylet and GCS: SIGTERM when made (SIGKILL where ``force``),
    SIGKILL once ``grace`` seconds have passed, and gone only when the pid
    has been waited for. What a process held, a chip above all, is free once
    ``gone()`` says so and not before: a process inside ``exit`` has lost
    its connections and its fd table while the kernel still releases its
    devices. ``kill`` replaces ``proc.kill`` where more than the process
    has to go (a worker's container)."""

    def __init__(self, proc: subprocess.Popen, grace: float = EXIT_GRACE_S,
                 force: bool = False, kill: Optional[Callable[[], None]] = None):
        self.proc = proc
        self.grace = grace
        self.since = time.monotonic()
        self._kill = kill or proc.kill
        self._killed = False
        if force:
            self.kill()
        else:
            try:
                proc.terminate()
            except OSError:
                pass

    def kill(self):
        if not self._killed:
            self._killed = True
            try:
                self._kill()
            except OSError:
                pass

    @property
    def age(self) -> float:
        return time.monotonic() - self.since

    @property
    def overdue(self) -> bool:
        """Killed a whole grace ago and still there: nothing more can be
        sent to it, the caller logs it and goes on."""
        return self.age >= 2 * self.grace

    def gone(self) -> bool:
        """One poll, never blocking: True once the pid has been reaped."""
        if self.proc.poll() is not None:
            return True
        if self.age >= self.grace:
            self.kill()
        return False

    def wait(self) -> bool:
        """Block until the process is reaped (True) or overdue (False)."""
        while not self.gone():
            if self.overdue:
                return False
            time.sleep(EXIT_POLL_S)
        return True


def _end(proc: subprocess.Popen, grace: float = EXIT_GRACE_S,
         force: bool = False):
    end = ProcessEnd(proc, grace, force=force)
    if not end.wait():
        logger.error("process pid=%s not reaped %.1fs after its signal",
                     proc.pid, end.age)


class NodeProcesses:
    """Starts GCS (head only) + raylet subprocesses for one logical node."""

    def __init__(
        self,
        head: bool = True,
        gcs_host: str = "127.0.0.1",
        gcs_port: Optional[int] = None,
        session_dir: Optional[str] = None,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
    ):
        self.head = head
        if head and not os.environ.get("RAY_TPU_CLUSTER_TOKEN"):
            # Cluster-scoped RPC auth: every process spawned from here (and
            # every driver sharing this env) inherits the token; rpcio
            # rejects unauthenticated connects (see rpcio.py preamble).
            import secrets

            os.environ["RAY_TPU_CLUSTER_TOKEN"] = secrets.token_hex(16)
        self.session_dir = session_dir or _make_session_dir()
        # Persist the token (0600) so separately launched processes — the
        # CLI after `start --head`, drivers using init(address=...), worker
        # raylets joining via `start --address` on the same host — can load
        # it instead of silently failing auth. Cross-host joins still export
        # RAY_TPU_CLUSTER_TOKEN manually (the CLI prints the hint).
        token = os.environ.get("RAY_TPU_CLUSTER_TOKEN", "")
        if token:
            self.token_file = os.path.join(self.session_dir, "cluster_token")
            if not os.path.exists(self.token_file):
                fd = os.open(
                    self.token_file, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600
                )
                with os.fdopen(fd, "w") as f:
                    f.write(token)
        else:
            self.token_file = None
        self.logs = os.path.join(self.session_dir, "logs")
        os.makedirs(self.logs, exist_ok=True)
        self.gcs_host = gcs_host
        self.gcs_proc: Optional[subprocess.Popen] = None
        suffix = uuid.uuid4().hex[:8]
        self.gcs_persist_path = os.path.join(self.session_dir, "gcs_store.log")
        if head:
            port_file = os.path.join(self.session_dir, f"gcs_port_{suffix}")
            with steptrace.span("init/gcs"):
                self.gcs_proc = _spawn(
                    [sys.executable, "-m", "ray_tpu._private.gcs_main",
                     "--host", gcs_host, "--port", "0",
                     "--port-file", port_file,
                     "--persist-path", self.gcs_persist_path,
                     "--cluster-id", os.path.basename(self.session_dir)],
                    os.path.join(self.logs, "gcs.out"),
                    env=package_env(),
                )
                self.gcs_port = int(_wait_port_file(port_file)[0])
        else:
            assert gcs_port is not None
            self.gcs_port = gcs_port
        raylet_port_file = os.path.join(self.session_dir, f"raylet_port_{suffix}")
        cmd = [
            sys.executable, "-m", "ray_tpu._private.raylet_main",
            "--gcs-host", gcs_host, "--gcs-port", str(self.gcs_port),
            "--session-dir", self.session_dir,
            "--port-file", raylet_port_file,
        ]
        if resources is not None:
            cmd += ["--resources", json.dumps(resources)]
        if labels is not None:
            cmd += ["--labels", json.dumps(labels)]
        with steptrace.span("init/raylet"):
            self.raylet_proc = _spawn(
                cmd, os.path.join(self.logs, f"raylet_{suffix}.out"),
                env=package_env(),
            )
            lines = _wait_port_file(raylet_port_file)
        self.raylet_port = int(lines[0])
        self.node_id = lines[1] if len(lines) > 1 else None

    @property
    def address(self) -> str:
        return f"{self.gcs_host}:{self.gcs_port}"

    def kill_raylet(self, graceful: bool = False):
        """Chaos hook (analog of ray: _private/test_utils.py NodeKillerActor)."""
        _end(self.raylet_proc, RAYLET_EXIT_GRACE_S, force=not graceful)

    # -- network chaos hooks (see _private/faultsim.py) -----------------
    # Every control-plane process spawned from here inherits
    # RAY_TPU_RPC_FAULTS / RAY_TPU_RPC_FAULTS_FILE through its env; the
    # FILE variant is re-read live, so faults can be armed and HEALED
    # while raylet/GCS subprocesses keep running. Export the env var
    # BEFORE building the cluster — children snapshot their env at spawn.

    def set_network_faults(self, spec: str):
        """(Re)write the live fault spec file. Requires
        RAY_TPU_RPC_FAULTS_FILE to have been exported before this node's
        processes started."""
        path = os.environ.get("RAY_TPU_RPC_FAULTS_FILE")
        assert path, (
            "export RAY_TPU_RPC_FAULTS_FILE before starting the cluster "
            "to use dynamic fault injection"
        )
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(spec)
        os.replace(tmp, path)  # atomic: readers never see a half-written spec

    def clear_network_faults(self):
        """Heal: remove every armed network fault."""
        self.set_network_faults("")

    def kill_gcs(self):
        """Chaos hook: kill the GCS process (head only). State survives in
        the persist log; ``restart_gcs`` brings it back on the same port."""
        assert self.gcs_proc is not None, "kill_gcs only valid on the head"
        _end(self.gcs_proc, force=True)

    def restart_gcs(self):
        """Restart the GCS on its original port; it replays the persist log
        and raylets/workers reconnect (ray: GCS FT via Redis restart +
        RayletNotifyGCSRestart)."""
        assert self.head, "restart_gcs only valid on the head"
        self.gcs_proc = _spawn(
            [sys.executable, "-m", "ray_tpu._private.gcs_main",
             "--host", self.gcs_host, "--port", str(self.gcs_port),
             "--persist-path", self.gcs_persist_path,
             "--cluster-id", os.path.basename(self.session_dir)],
            os.path.join(self.logs, "gcs.out"),
            env=package_env(),
        )

    def shutdown(self):
        """End this node's processes: the raylet, which ends its workers
        and waits for each of them (``Raylet.stop``), then the GCS. When
        this returns, every process the session started on this node has
        been reaped, so a chip one of them held can be opened; one that
        outlived its SIGKILL by a whole grace is logged by pid instead."""
        _end(self.raylet_proc, RAYLET_EXIT_GRACE_S)
        if self.gcs_proc is not None:
            _end(self.gcs_proc)
        # release this node's share of /dev/shm: the store dir (slab
        # segments, index, .obj files) is dead weight once the raylet is
        # gone — processes still holding mappings keep their pages until
        # the views die, so this is safe for stragglers
        if self.node_id:
            import shutil

            shutil.rmtree(
                os.path.join(self.session_dir, f"store_{self.node_id[:12]}"),
                ignore_errors=True,
            )
