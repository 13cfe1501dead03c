"""Train worker gang.

ray parity: python/ray/train/_internal/worker_group.py:100 (WorkerGroup of
RayTrainWorker actors) — a gang of actors, one per host-worker, created
inside a placement group, each running the user train loop on a session
thread and draining a result queue.
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Any, Callable, List, Optional

import ray_tpu
from ray_tpu._private import steptrace
from ray_tpu.air.checkpoint import Checkpoint, finish_commit
from ray_tpu.train import session as session_mod


@ray_tpu.remote
class TrainWorker:
    """ray parity: worker_group.py:18 RayTrainWorker."""

    def __init__(self):
        self._thread: Optional[threading.Thread] = None
        self._session = None
        self._final: Optional[dict] = None

    def setup_session(self, rank: int, world_size: int, local_rank: int,
                      node_rank: int, experiment_name: str, trial_id: str,
                      trial_dir: str, checkpoint: Optional[Checkpoint]):
        with steptrace.span("gang/session"):
            ctx = session_mod.TrainContext(
                rank=rank, world_size=world_size, local_rank=local_rank,
                node_rank=node_rank, experiment_name=experiment_name,
                trial_id=trial_id, trial_dir=trial_dir,
            )
            self._session = session_mod.init_session(ctx, checkpoint)
        return True

    def execute(self, fn: Callable, *args, **kwargs):
        """Run an arbitrary callable on the worker (backend setup hooks)."""
        return fn(*args, **kwargs)

    def _rt_init_collective(self, world_size, rank, backend, group_name,
                            epoch=0, quant=""):
        from ray_tpu.util import collective as col

        col.init_collective_group(world_size, rank, backend, group_name,
                                  epoch=epoch, quant=quant)
        return rank

    def ping(self):
        """Liveness probe: a dead worker raises ActorDiedError at the
        caller; a live one answers immediately (the gang is created with
        max_concurrency>1 so this never queues behind next_result)."""
        return True

    def health(self):
        """Progress snapshot for the executor's per-step watchdog."""
        return session_mod.health()

    def request_drain(self):
        """Preemption notice: checkpoint at the next step boundary and
        exit cleanly (same path the worker's SIGTERM handler takes)."""
        return session_mod.request_drain()

    def start_training(self, train_fn: Callable, config: dict):
        assert self._session is not None, "setup_session must run first"
        received = time.time()
        sess = self._session
        shards = config.pop("__datasets__", None)
        if shards:
            rank = sess.ctx.get_world_rank()
            sess.dataset_shards = {
                name: splits[rank] for name, splits in shards.items()
            }

        def _run():
            try:
                import inspect

                sig = inspect.signature(train_fn)
                # ``gang/loop``: this call received -> the user's loop
                # about to run on its thread
                steptrace.record_phase("gang/loop", received, time.time())
                try:
                    if len(sig.parameters) >= 1:
                        train_fn(config)
                    else:
                        train_fn()
                except SystemExit:  # a drain or a stop, raised by report()
                    pass
                # no done with a save outstanding: its checkpoint goes on
                # the queue first, and a commit that failed is the error
                finish_commit()
                last = {"type": "done"}
            except BaseException as e:  # noqa: BLE001
                last = {
                    "type": "error",
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc(),
                }
            # before the driver can know: it kills the gang at this message
            sess.loop_over = True
            sess.queue.put(last)

        self._thread = threading.Thread(target=_run, name="train-loop", daemon=True)
        self._thread.start()
        return True

    def next_result(self, timeout: float = 300.0):
        """Block for the next report/done/error from the train loop. An
        empty poll piggybacks the session health snapshot so the
        executor's watchdog sees per-rank step progress without a second
        RPC round."""
        import queue as _q

        try:
            return self._session.queue.get(timeout=timeout)
        except _q.Empty:
            return {"type": "timeout", "health": session_mod.health()}

    def request_stop(self):
        if self._session:
            self._session.stop_requested.set()
        return True

    def shutdown_session(self):
        session_mod.shutdown_session()
        return True


class WorkerGroup:
    def __init__(self, num_workers: int, resources_per_worker: dict,
                 placement_group=None, runtime_env: Optional[dict] = None,
                 generation: int = 0):
        from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy

        self.num_workers = num_workers
        # gang generation: 0 on first placement, bumped by the executor on
        # each recovery re-placement; threaded into the collective group
        # epoch so the re-formed gang's rendezvous keys are fresh
        self.generation = generation
        self.workers: List = []
        for i in range(num_workers):
            # max_concurrency=4: liveness pings and health polls must
            # interleave with the long-blocking next_result call
            opts = dict(resources=dict(resources_per_worker), num_cpus=0,
                        max_concurrency=4)
            if placement_group is not None:
                opts["scheduling_strategy"] = PlacementGroupSchedulingStrategy(
                    placement_group, placement_group_bundle_index=i
                )
            if runtime_env:
                opts["runtime_env"] = runtime_env
            self.workers.append(TrainWorker.options(**opts).remote())

    def execute(self, fn: Callable, *args, **kwargs) -> List[Any]:
        return ray_tpu.get(
            [w.execute.remote(fn, *args, **kwargs) for w in self.workers],
            timeout=600,
        )

    def execute_single(self, index: int, fn: Callable, *args, **kwargs) -> Any:
        return ray_tpu.get(
            self.workers[index].execute.remote(fn, *args, **kwargs), timeout=600
        )

    def execute_async(self, fn: Callable, *args, **kwargs):
        return [w.execute.remote(fn, *args, **kwargs) for w in self.workers]

    def shutdown(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
