"""Node resource autodetection.

Analog of ray: python/ray/_private/resource_spec.py, with the TPU delta the
reference lacks (its accelerators are NVIDIA-only,
ray: python/ray/_private/resource_spec.py:175-182,
util/accelerators/accelerators.py:1-7): TPU chips are a first-class "TPU"
resource, and ICI topology is advertised as node labels so placement-group
STRICT_PACK can target one slice. Detection is env-driven
(TPU_CHIP_COUNT / TPU_TOPOLOGY / TPU_WORKER_ID, as set by GKE / QR runtimes).
The opt-in probe (config flag tpu_autodetect) counts the chips' device nodes:
this runs inside the long-lived raylet, and asking jax.devices() there would
open the device library and keep the chips from every worker.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

from ray_tpu._private.config import GLOBAL_CONFIG as cfg


def tpu_device_nodes() -> List[str]:
    """This host's chips, by their device nodes (/dev/accel* on older
    generations, /dev/vfio/<n> since v5e) — opens nothing."""
    return sorted(glob.glob("/dev/accel[0-9]*")
                  or glob.glob("/dev/vfio/[0-9]*"))


def detect_resources() -> Tuple[Dict[str, float], Dict[str, str]]:
    resources: Dict[str, float] = {}
    labels: Dict[str, str] = {}
    resources["CPU"] = float(os.cpu_count() or 1)
    try:
        import psutil

        mem = psutil.virtual_memory().total
    except Exception:
        mem = 8 * 1024**3
    resources["memory"] = float(int(mem * 0.7))
    resources["object_store_memory"] = float(cfg.object_store_memory)

    chips = os.environ.get("TPU_CHIP_COUNT")
    if chips is None and cfg.tpu_autodetect:
        chips = str(len(tpu_device_nodes()))
    if chips:
        n = float(chips)
        if n > 0:
            resources["TPU"] = n
            accel = os.environ.get("TPU_ACCELERATOR_TYPE")
            if accel:
                labels["tpu-accelerator-type"] = accel
                resources[f"TPU-{accel}"] = n
    topo = os.environ.get("TPU_TOPOLOGY")
    if topo:
        labels["tpu-topology"] = topo
    slice_name = os.environ.get("TPU_SLICE_NAME") or os.environ.get("TPU_NAME")
    if slice_name:
        labels["tpu-slice"] = slice_name
    worker_id = os.environ.get("TPU_WORKER_ID")
    if worker_id is not None:
        labels["tpu-worker-id"] = worker_id
    return resources, labels
