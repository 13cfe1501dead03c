"""Checkpoint abstraction (ray parity: python/ray/air/checkpoint.py:66 and
the file-based train/_checkpoint.py:30).

A Checkpoint is a directory (canonical form) or an in-memory dict that
morphs to/from a directory. JAX pytrees checkpoint via orbax when available
(msgpack fallback), so trainer state is TPU-native (sharded-array-aware)
rather than torch-pickled.
"""

from __future__ import annotations

import json
import os
import pickle
import shutil
import tempfile
import threading
import uuid
from typing import Any, Callable, Dict, Optional

from ray_tpu._private import steptrace

_DICT_FILE = "checkpoint_dict.pkl"


class Checkpoint:
    def __init__(self, path: Optional[str] = None,
                 _data: Optional[Dict[str, Any]] = None):
        self._path = path
        self._data = _data

    # -- constructors ---------------------------------------------------
    @classmethod
    def from_dict(cls, data: Dict[str, Any]) -> "Checkpoint":
        return cls(_data=dict(data))

    @classmethod
    def from_directory(cls, path: str) -> "Checkpoint":
        return cls(path=path)

    # -- accessors ------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        if self._data is not None:
            return dict(self._data)
        f = os.path.join(self._path, _DICT_FILE)
        if os.path.exists(f):
            with open(f, "rb") as fh:
                return pickle.load(fh)
        raise ValueError(f"checkpoint at {self._path} has no dict payload")

    def to_directory(self, path: Optional[str] = None) -> str:
        path = path or os.path.join(tempfile.gettempdir(),
                                    f"rt_ckpt_{uuid.uuid4().hex[:8]}")
        os.makedirs(path, exist_ok=True)
        if self._path is not None and os.path.abspath(self._path) != os.path.abspath(path):
            for item in os.listdir(self._path):
                src = os.path.join(self._path, item)
                dst = os.path.join(path, item)
                if os.path.isdir(src):
                    shutil.copytree(src, dst, dirs_exist_ok=True)
                else:
                    shutil.copy2(src, dst)
        if self._data is not None:
            with open(os.path.join(path, _DICT_FILE), "wb") as fh:
                pickle.dump(self._data, fh, protocol=5)
        return path

    def as_directory(self):
        """Context manager yielding a directory view."""
        import contextlib

        @contextlib.contextmanager
        def _cm():
            if self._path is not None and self._data is None:
                yield self._path
            else:
                tmp = self.to_directory()
                try:
                    yield tmp
                finally:
                    shutil.rmtree(tmp, ignore_errors=True)

        return _cm()

    @property
    def path(self) -> Optional[str]:
        return self._path

    def __repr__(self):
        return f"Checkpoint(path={self._path!r}, in_memory={self._data is not None})"


class _Commit:
    """An orbax save whose tree is on the host and whose files a thread is
    still writing, behind the train loop's next steps. The thread records
    the write as the span ``save/commit`` (bytes of the tree as its count)
    and keeps what went wrong in ``error`` for the loop to raise."""

    def __init__(self, ckptr, directory: str, nbytes: int):
        self.directory = os.path.abspath(directory)
        self.nbytes = nbytes
        self.error: Optional[BaseException] = None
        self._lock = threading.Lock()
        self._ended = False
        self._hand_over: Optional[Callable[[], None]] = None
        self._thread = threading.Thread(
            target=self._run, args=(ckptr,), name="save-commit", daemon=True)
        self._thread.start()

    def _run(self, ckptr):
        try:
            with steptrace.span("save/commit", self.nbytes):
                ckptr.wait_until_finished()
        except BaseException as e:  # noqa: BLE001 - raised again by wait()
            self.error = e
        with self._lock:
            self._ended = True
            hand_over = self._hand_over
        if hand_over is not None and self.error is None:
            hand_over()

    def writes(self, path: str) -> bool:
        """Whether files at or under ``path`` are still being written."""
        path = os.path.abspath(path)
        return not self._ended and (
            self.directory == path
            or self.directory.startswith(path + os.sep))

    def then(self, hand_over: Callable[[], None]) -> None:
        """Call ``hand_over`` once the files are whole: from the commit's
        thread when the write ends, here if it has ended, never if it
        failed (``wait`` raises that)."""
        with self._lock:
            if not self._ended:
                self._hand_over = hand_over
                return
        if self.error is None:
            hand_over()

    def wait(self) -> None:
        self._thread.join()
        if self.error is not None:
            raise self.error


# The one commit a train session may have in flight (save_pytree). Only the
# train loop's thread reads or sets it; the commit's own thread does not.
_in_flight: Optional[_Commit] = None


def commit_in_flight() -> Optional[_Commit]:
    """The last commit ``save_pytree`` left behind a train session, ended
    or not; one that failed raises its error here and is forgotten."""
    if _in_flight is not None and _in_flight.error is not None:
        finish_commit()
    return _in_flight


def finish_commit() -> None:
    """Wait until no save is being written. Raises what a commit failed
    with, once: an unacknowledged save is the loop's error, as it would
    have been from a synchronous save."""
    global _in_flight
    commit, _in_flight = _in_flight, None
    if commit is not None:
        commit.wait()


def save_pytree(tree, directory: str, name: str = "params"):
    """Checkpoint a JAX pytree (orbax if available, msgpack fallback).

    Outside a train session the files are whole when this returns. Inside
    one (``train.session.get_session()``) an orbax save returns once the
    tree is on the host, so the caller may overwrite or donate the device
    buffers at once, and the files are written behind the steps that
    follow. At most one such commit is in flight: the next ``save_pytree``
    first waits for it. A checkpoint of ``directory`` counts as handed
    over only once its files are whole: ``train.report(checkpoint=)``
    holds it back until then, the session waits for it before the loop's
    ``done``, and a commit that fails raises in the loop at the next
    ``save_pytree``, ``report`` or that wait. A process killed during a
    commit leaves the save before it whole and no directory under this
    save's name (orbax renames a temporary directory last).

    Step observatory: three spans, bytes of the tree as their count.
    ``ckpt/setup`` is what every call pays before a byte moves,
    ``ckpt/snapshot`` the copy off the device (orbax: ``save()`` returns
    once the tree is on the host and a thread has the write),
    ``ckpt/commit`` the time the caller is blocked on files: outside a
    session the wait for this save's, inside one the wait, before the
    snapshot, for the previous save's (its bytes as the count). There
    the write itself is ``save/commit``, from the commit's thread."""
    global _in_flight
    import jax

    from ray_tpu.train import session as train_session

    with steptrace.span("ckpt/setup"):
        os.makedirs(directory, exist_ok=True)
        target = os.path.join(directory, name)
        nbytes = sum(getattr(leaf, "nbytes", 0)
                     for leaf in jax.tree_util.tree_leaves(tree))
        try:
            import orbax.checkpoint as ocp

            ckptr = ocp.StandardCheckpointer()
        except Exception:
            ckptr = None
    behind = ckptr is not None and train_session.get_session() is not None
    if behind:
        with steptrace.span("ckpt/commit") as waited:
            waited.n = _in_flight and _in_flight.nbytes
            finish_commit()
    if ckptr is not None:
        try:
            with steptrace.span("ckpt/snapshot", nbytes):
                ckptr.save(os.path.abspath(target) + "_orbax", tree,
                           force=True)
            if behind:
                _in_flight = _Commit(ckptr, directory, nbytes)
            else:
                with steptrace.span("ckpt/commit", nbytes):
                    ckptr.wait_until_finished()
            return
        except Exception:
            pass
    from flax import serialization

    with steptrace.span("ckpt/snapshot", nbytes):
        data = serialization.to_bytes(tree)
    with steptrace.span("ckpt/commit", len(data)):
        with open(target + ".msgpack", "wb") as f:
            f.write(data)


def load_pytree(directory: str, target, name: str = "params"):
    path = os.path.join(directory, name)
    orbax_path = os.path.abspath(path) + "_orbax"
    if os.path.exists(orbax_path):
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        return ckptr.restore(orbax_path, target)
    from flax import serialization

    with open(path + ".msgpack", "rb") as f:
        return serialization.from_bytes(target, f.read())
