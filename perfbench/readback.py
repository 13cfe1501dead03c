"""Read acknowledged checkpoints back and hold them against the state they
were taken from.

Run by the driver as a process of its own, after ``fit()`` has returned
and the cluster is down, with ``JAX_PLATFORMS=cpu``: orbax needs jax, the
driver must never import it, and the chip is not this process's to touch.

    python perfbench/readback.py <expect.json>

``expect.json``: {"trial_dir", "leaf_paths", "saves": [{"index", "sums"}]}
where ``sums`` are the worker's per-leaf checksums of the device state at
the save (perfbench/worker.py:_checksums) and ``index`` numbers the
checkpoints in the order ``fit()`` persisted them. Prints one JSON line:
{"read_back": n, "failed": n, "problems": [...]}.
"""

from __future__ import annotations

import json
import os
import re
import sys

import numpy as np

_WORDS = {1: np.uint8, 2: np.uint16, 4: np.uint32}


def checksum(x: np.ndarray) -> list:
    """Sum of the words and position-weighted sum, modulo 2**32."""
    w = np.ascontiguousarray(x).reshape(-1).view(
        _WORDS[x.dtype.itemsize]).astype(np.uint32)
    k = np.arange(w.shape[0], dtype=np.uint32) % np.uint32(65521) + np.uint32(1)
    return [int(w.sum(dtype=np.uint32)), int((w * k).sum(dtype=np.uint32))]


def _restore(path: str):
    import jax
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    cpu = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
    target = jax.tree.map(
        lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=cpu),
        ckptr.metadata(path).item_metadata.tree)
    return ckptr.restore(path, target)


def leaves_by_name(tree) -> dict:
    """{"opt_state/0/mu/wte/embedding": leaf}: names that are the same for
    the tree that was saved (namedtuples) and the one restored (dicts)."""
    import jax

    return {"/".join(re.findall(r"[A-Za-z0-9_]+", jax.tree_util.keystr(p))):
            leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def verify(expect: dict) -> dict:
    problems, failed = [], 0
    for save in expect["saves"]:
        path = os.path.join(expect["trial_dir"],
                            f"checkpoint_{save['index']:06d}", "state_orbax")
        try:
            leaves = leaves_by_name(_restore(path))
            bad = [name for name, want in zip(expect["leaf_paths"],
                                              save["sums"])
                   if name not in leaves
                   or checksum(np.asarray(leaves[name])) != want]
            if len(leaves) != len(expect["leaf_paths"]):
                bad.append(f"{len(leaves)} leaves on disk, "
                           f"{len(expect['leaf_paths'])} saved")
        except Exception as e:  # a save that cannot be read did not read back
            bad = [f"{type(e).__name__}: {e}"[:300]]
        if bad:
            failed += 1
            problems.append({"index": save["index"], "leaves": bad[:5]})
    return {"read_back": len(expect["saves"]), "failed": failed,
            "problems": problems}


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        print(json.dumps(verify(json.load(f))), flush=True)
