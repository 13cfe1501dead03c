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
import uuid
from typing import Any, Dict, Optional

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


def save_pytree(tree, directory: str, name: str = "params"):
    """Checkpoint a JAX pytree (orbax if available, msgpack fallback).

    Step observatory: three spans, bytes of the tree as their count.
    ``ckpt/setup`` is what every call pays before a byte moves,
    ``ckpt/snapshot`` the copy off the device (orbax: ``save()`` returns
    once the tree is on the host and a thread has the write),
    ``ckpt/commit`` the wait for the files."""
    import jax

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
    if ckptr is not None:
        try:
            with steptrace.span("ckpt/snapshot", nbytes):
                ckptr.save(os.path.abspath(target) + "_orbax", tree,
                           force=True)
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
