"""What every Pallas kernel of ``ray_tpu/ops`` asks of its surroundings:
whether a Mosaic call may run where an operand is traced, how it is handed
to a mesh, the compiler's parameters and the VMEM a core has. The one place
of ``ops/`` that reads the backend or the device for a kernel, wraps one in
``shard_map`` or writes ``pltpu.CompilerParams``; a kernel module keeps what is its own (which
shapes fit, which operands are split by rows, its numbers).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from ray_tpu.parallel.mesh_utils import traced_mesh_axes


def batch_axes(x, who: str):
    """(mesh, axes): the mesh ``x`` is traced under and those of its axes
    the batch (leading) dim is split over — the repo's data-like axes
    (``mesh_utils.data_sharding``) of size > 1 that an enclosing
    ``shard_map`` has not already split. ``axes`` is empty outside a mesh.
    A leading dim those axes do not divide is an error, not a reason to
    leave the kernel to the partitioner, which refuses it; the error names
    ``who``, the op that asked."""
    mesh, axes, _ = traced_mesh_axes(x)
    n = math.prod(mesh.shape[a] for a in axes)
    if x.shape[0] % n:
        raise ValueError(
            f"{who}: leading dim {x.shape[0]} is not divisible by "
            f"the mesh's batch axes {axes} (size {n}); the Pallas kernel "
            "runs per batch shard and cannot be partitioned otherwise")
    return mesh, axes


def unmapped_mesh_axes(x) -> tuple:
    """Axes of size > 1 of the mesh ``x`` is traced under that neither
    ``per_batch_shard`` maps the batch over nor an enclosing ``shard_map``
    has made manual (``model`` under tensor parallelism, ``seq``,
    ``expert``). Under any of them the kernel reaches the partitioner,
    which refuses it ("Mosaic kernels cannot be automatically
    partitioned"): a caller that chooses between paths asks here first."""
    return traced_mesh_axes(x)[2]


def takes_kernels(x) -> bool:
    """Whether an op's ``impl=None`` may take its Pallas kernels for the
    operand ``x``: on a TPU, and under no mesh axis of more than one device
    but the batch's (``data`` / ``fsdp``, over which ``per_batch_shard``
    runs the kernel a batch shard each). Any other live axis would leave
    the Mosaic call to the partitioner, which refuses it, so there the op
    runs its twin (XLA's attention, the chunked scan, the ``jnp`` form) as
    it does off a TPU. Asked when the call is traced, of the backend and
    ``x``'s type alone; whether the shapes fit the kernels is the op's own
    question."""
    return jax.default_backend() == "tpu" and not unmapped_mesh_axes(x)


def takes_unmapped_kernel(x) -> bool:
    """``takes_kernels`` for a call with no batch to map over (``ops.moe``'s
    ``unwritten``): a live batch axis refuses it too."""
    return takes_kernels(x) and not traced_mesh_axes(x)[1]


def per_batch_shard(fn, x, split: Sequence[bool], who: str):
    """``fn`` as it is outside a mesh; under a mesh whose data-like axes
    split ``x``'s batch (``batch_axes``) ``fn`` a batch shard each, inside
    ``shard_map`` over those axes: the partitioner refuses Mosaic calls, and
    the rows of a batch are independent. ``split`` says, operand by operand,
    which are split by rows (the others go in whole); the result is split
    by rows."""
    mesh, axes = batch_axes(x, who)
    if not axes:
        return fn
    rows, whole = PartitionSpec(axes), PartitionSpec()
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(rows if s else whole for s in split),
        out_specs=rows, axis_names=set(axes), check_vma=False)


# VMEM of a core, by the device's kind, for a kernel that asks for more than
# the compiler's own 16 MiB. A kind stands here once such a kernel was read on
# it (``benches/grouped_matmul.py``: the v5e); on any other the kernel's tiles
# find no room and its caller keeps its twin, where a limit the core does not
# have would fail the step's compilation.
_VMEM_BYTES = {"TPU v5 lite": 128 * 2**20}


def device_kind() -> str:
    return jax.devices()[0].device_kind


def vmem_bytes() -> int:
    """VMEM of one core of the backend's devices; 0 for a kind that
    ``_VMEM_BYTES`` does not know."""
    return _VMEM_BYTES.get(device_kind(), 0)


def compiler_params(interpret: bool, semantics: Sequence[str],
                    vmem_limit_bytes: Optional[int] = None):
    """A ``pallas_call``'s ``compiler_params``: how each grid axis may be
    split or ordered and what VMEM the kernel asks for (None: the
    compiler's own 16 MiB); nothing in interpret mode."""
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics),
                                vmem_limit_bytes=vmem_limit_bytes)
