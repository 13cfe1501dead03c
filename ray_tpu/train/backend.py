"""Backend plugin interface + JAX and Torch backends.

ray parity: python/ray/train/backend.py:41,53 (Backend/BackendConfig) and the
framework configs (torch/config.py:29 TorchConfig + :69
_setup_torch_process_group, tensorflow/config.py TF_CONFIG). The TPU-native
backend is JaxConfig: instead of a NCCL process group, workers form a JAX
distributed system — one worker process per host owning all local chips,
``jax.distributed.initialize`` keyed by the worker group, collectives riding
ICI inside jitted steps.
"""

from __future__ import annotations

import errno
import glob
import os
import socket
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ray_tpu._private import steptrace


@dataclass
class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


class Backend:
    def on_start(self, worker_group, backend_config):
        pass

    def on_training_start(self, worker_group, backend_config):
        pass

    def on_shutdown(self, worker_group, backend_config):
        pass


# ---------------------------------------------------------------------------
# JAX backend (the TPU path)
# ---------------------------------------------------------------------------


@dataclass
class JaxConfig(BackendConfig):
    """Per-worker JAX setup.

    distributed: "auto" initializes jax.distributed only for multi-worker
    TPU gangs (multi-host pods); "off" leaves workers as independent JAX
    processes whose host-level sync goes through ray_tpu.util.collective;
    "force" always initializes.

    overlap_grads arms ``session.GradSync`` overlap on every worker:
    gradient allreduces dispatch on a background thread so their chunked
    collective spans interleave with the step's compute phase spans.
    collective_quant ("int8") makes the train_dp group's SUM/MEAN
    allreduces ride the block-quantized wire format.
    """

    distributed: str = "auto"
    use_tpu: bool = False
    env_vars: Dict[str, str] = field(default_factory=dict)
    overlap_grads: bool = False
    collective_quant: str = ""

    @property
    def backend_cls(self):
        return _JaxBackend


# How long a TPU gang's worker waits for the host's chips before it opens
# them. The longest release seen after a four-chip job's worker exited is
# 16 s (PERF.md section 7); a predecessor still draining may take
# train_drain_grace_s (30 s) more.
CHIPS_FREE_DEADLINE_S = 60.0
_CHIPS_POLL_S = 0.1


def _chip_is_held(node: str) -> bool:
    """Whether another process still holds this chip node. The probe is an
    open and a close of the node, as jax's own open will be: a VFIO group
    admits one opener. Reading /proc/*/fd instead would open nothing and
    see nothing: a holder inside ``exit`` has no fd table left while its
    nodes stay busy (on the chip /proc named the holder of one of four
    held nodes as the job returned and of none after, for the 9.4 s until
    the last opened: PERF.md section 7). The probe runs in the process
    that is about to open the chips and is their one rightful opener (the
    runtime sets no chip visibility: a TPU worker's jax opens every chip of
    the host), so it takes no chip from anyone."""
    try:
        os.close(os.open(node, os.O_RDWR))
    except OSError as e:
        # any other error is not this wait's to judge: jax's open will say
        return e.errno == errno.EBUSY
    return False


def _busy_chip_node() -> Optional[str]:
    """The first of this host's chip nodes that is still held, or None."""
    from ray_tpu._private.resource_spec import tpu_device_nodes

    return next((n for n in tpu_device_nodes() if _chip_is_held(n)), None)


def _chip_holders(node: str) -> List[int]:
    """Pids that /proc shows with ``node`` open. Opens nothing; empty for
    a holder that is exiting, or another user's."""
    pids = []
    for fd in glob.glob("/proc/[0-9]*/fd/*"):
        try:
            if os.readlink(fd) == node:
                pids.append(int(fd.split("/")[2]))
        except OSError:
            pass
    return sorted(set(pids))


def _wait_for_chips() -> float:
    """Wait until every chip node of this host can be opened, at most
    CHIPS_FREE_DEADLINE_S; log and return the seconds waited (the span
    ``gang/chip_wait`` holds the same, its count the probes made). The job
    before on this host may have returned with its worker still exiting
    (an older runtime, a killed driver or raylet), and jax's open of a
    held chip fails once, from the user's loop, with no retry."""
    start = time.monotonic()
    with steptrace.span("gang/chip_wait", 1) as probes:
        while (node := _busy_chip_node()) is not None:
            waited = time.monotonic() - start
            if waited >= CHIPS_FREE_DEADLINE_S:
                holders = _chip_holders(node)
                raise RuntimeError(
                    f"{node} is still held after {waited:.1f} s"
                    + (f" by pid {', '.join(map(str, holders))}" if holders
                       else " (no live holder in /proc: a process still exiting,"
                            " or another user's)")
                    + ": another job on this host has the chips")
            time.sleep(_CHIPS_POLL_S)
            probes.n += 1
    waited = time.monotonic() - start
    print(f"ray_tpu: waited {waited:.1f} s for this host's chips", flush=True)
    return waited


def _jax_worker_setup(coordinator: Optional[str], num_processes: int,
                      process_id: int, env_vars: Dict[str, str],
                      use_tpu: bool):
    for k, v in env_vars.items():
        os.environ[k] = str(v)
    if use_tpu:
        _wait_for_chips()
    if coordinator is not None:
        import jax

        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    return True


def _enable_overlap():
    from ray_tpu.train import session

    session.set_overlap_grads(True)
    return True


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get_host() -> str:
    return socket.gethostbyname(socket.gethostname())


class _JaxBackend(Backend):
    def on_start(self, worker_group, config: JaxConfig):
        n = worker_group.num_workers
        coordinator = None
        if config.distributed == "force" or (
            config.distributed == "auto" and config.use_tpu and n > 1
        ):
            host = worker_group.execute_single(0, _get_host)
            coordinator = f"{host}:{_free_port()}"
        import ray_tpu

        refs = []
        for i, w in enumerate(worker_group.workers):
            refs.append(
                w.execute.remote(
                    _jax_worker_setup, coordinator, n, i,
                    dict(config.env_vars), config.use_tpu,
                )
            )
        ray_tpu.get(refs, timeout=300)
        if config.overlap_grads:
            ray_tpu.get(
                [w.execute.remote(_enable_overlap) for w in worker_group.workers],
                timeout=300,
            )
        # Host-level collective group for out-of-graph sync (weight
        # broadcast, metric reduction) — the Gloo-analog path.
        if n > 1:
            from ray_tpu.util import collective as col

            # epoch = gang generation: a recovery re-placement must not
            # rendezvous against the dead generation's KV state
            col.create_collective_group(
                worker_group.workers, n, list(range(n)),
                backend="store", group_name="train_dp",
                epoch=getattr(worker_group, "generation", 0),
                quant=config.collective_quant,
            )


# ---------------------------------------------------------------------------
# Torch backend (CPU gloo — API parity for reference workloads)
# ---------------------------------------------------------------------------


@dataclass
class TorchConfig(BackendConfig):
    backend: str = "gloo"
    init_method: str = "tcp"
    timeout_s: int = 1800

    @property
    def backend_cls(self):
        return _TorchBackend


def _torch_worker_setup(master_addr: str, master_port: int, rank: int,
                        world_size: int, backend: str, timeout_s: int):
    """ray parity: train/torch/config.py:69 _setup_torch_process_group."""
    import datetime

    import torch.distributed as dist

    if dist.is_initialized():
        return True
    os.environ["MASTER_ADDR"] = master_addr
    os.environ["MASTER_PORT"] = str(master_port)
    os.environ["RANK"] = str(rank)
    os.environ["WORLD_SIZE"] = str(world_size)
    dist.init_process_group(
        backend=backend,
        init_method=f"tcp://{master_addr}:{master_port}",
        rank=rank,
        world_size=world_size,
        timeout=datetime.timedelta(seconds=timeout_s),
    )
    return True


class _TorchBackend(Backend):
    def on_start(self, worker_group, config: TorchConfig):
        import ray_tpu

        master_addr = "127.0.0.1"
        master_port = _free_port()
        refs = []
        for i, w in enumerate(worker_group.workers):
            refs.append(
                w.execute.remote(
                    _torch_worker_setup, master_addr, master_port, i,
                    worker_group.num_workers, config.backend, config.timeout_s,
                )
            )
        ray_tpu.get(refs, timeout=300)

    def on_shutdown(self, worker_group, config):
        def _destroy():
            import torch.distributed as dist

            if dist.is_initialized():
                dist.destroy_process_group()
            return True

        try:
            worker_group.execute(_destroy)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# TensorFlow backend (TF_CONFIG — API parity for reference workloads)
# ---------------------------------------------------------------------------


@dataclass
class TensorflowConfig(BackendConfig):
    """ray parity: train/tensorflow/config.py — wires the TF_CONFIG env var
    (cluster spec + task index) on every worker so
    tf.distribute.MultiWorkerMirroredStrategy discovers the gang."""

    @property
    def backend_cls(self):
        return _TensorflowBackend


def _tf_grab_port() -> str:
    return f"{_get_host()}:{_free_port()}"


def _tf_worker_setup(tf_config: Dict):
    import json

    os.environ["TF_CONFIG"] = json.dumps(tf_config)
    return True


class _TensorflowBackend(Backend):
    def on_start(self, worker_group, config: TensorflowConfig):
        import ray_tpu

        # one fan-out round trip, not N serialized ones
        addrs = ray_tpu.get(
            [w.execute.remote(_tf_grab_port) for w in worker_group.workers],
            timeout=300,
        )
        refs = []
        for i, w in enumerate(worker_group.workers):
            refs.append(w.execute.remote(_tf_worker_setup, {
                "cluster": {"worker": addrs},
                "task": {"type": "worker", "index": i},
            }))
        ray_tpu.get(refs, timeout=300)

    def on_shutdown(self, worker_group, config):
        def _clear():
            os.environ.pop("TF_CONFIG", None)
            return True

        try:
            worker_group.execute(_clear)
        except Exception:
            pass
