"""TPU autodetection for node resource defaults.

Equivalent of the reference's ``python/ray/_private/accelerator.py``
(``_autodetect_num_tpus :153`` — counts ``/dev/accel*`` / vfio entries;
version probing via GCE metadata ``:175-220``). Metadata probing is omitted
(zero-egress environments); the generation can be supplied via env or labels.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Sequence

TPU_VERSION_ENV = "RT_TPU_VERSION"          # e.g. "v5p", "v5e"
NUM_TPUS_ENV = "RT_NUM_TPUS"
SLICE_NAME_ENV = "RT_TPU_SLICE_NAME"
SLICE_TOPOLOGY_ENV = "RT_TPU_SLICE_TOPOLOGY"
WORKER_ID_ENV = "RT_TPU_WORKER_ID"


def autodetect_num_tpu_chips() -> int:
    if NUM_TPUS_ENV in os.environ:
        return int(os.environ[NUM_TPUS_ENV])
    accel = glob.glob("/dev/accel*")
    if accel:
        return len(accel)
    vfio = glob.glob("/dev/vfio/[0-9]*")
    if vfio:
        return len(vfio)
    return 0


# GKE's TPU device-plugin webhook injects these into TPU pods (the
# reference reads the analogous GKE env at ``ray_constants.py:488`` and GCE
# metadata via RAY_GCE_TPU_ACCELERATOR_ENDPOINT ``:494``). Mapping them
# here means a pod scheduled by the GKE provider registers with the same
# slice labels a TPU-VM node would — zero extra plumbing in node_main.
GKE_WORKER_ID_ENV = "TPU_WORKER_ID"
GKE_TOPOLOGY_ENV = "TPU_TOPOLOGY"
GKE_ACCELERATOR_ENV = "TPU_ACCELERATOR_TYPE"
GKE_SLICE_NAME_ENV = "TPU_NAME"


def gke_node_labels() -> Dict[str, str]:
    """Slice labels from GKE-injected pod env (empty off-GKE)."""
    from ray_tpu.core import resources as res

    labels: Dict[str, str] = {}
    if GKE_ACCELERATOR_ENV in os.environ:
        labels[res.LABEL_ACCELERATOR_TYPE] = (
            "TPU-" + os.environ[GKE_ACCELERATOR_ENV].split("-")[0].upper())
    if GKE_SLICE_NAME_ENV in os.environ:
        labels[res.LABEL_SLICE_NAME] = os.environ[GKE_SLICE_NAME_ENV]
    if GKE_TOPOLOGY_ENV in os.environ:
        labels[res.LABEL_SLICE_TOPOLOGY] = os.environ[GKE_TOPOLOGY_ENV]
    if GKE_WORKER_ID_ENV in os.environ:
        labels[res.LABEL_WORKER_ID_IN_SLICE] = os.environ[GKE_WORKER_ID_ENV]
    return labels


def tpu_node_labels() -> Dict[str, str]:
    from ray_tpu.core import resources as res

    labels: Dict[str, str] = gke_node_labels()
    version = os.environ.get(TPU_VERSION_ENV)
    if version:
        labels[res.LABEL_ACCELERATOR_TYPE] = f"TPU-{version.upper()}"
    if SLICE_NAME_ENV in os.environ:
        labels[res.LABEL_SLICE_NAME] = os.environ[SLICE_NAME_ENV]
    if SLICE_TOPOLOGY_ENV in os.environ:
        labels[res.LABEL_SLICE_TOPOLOGY] = os.environ[SLICE_TOPOLOGY_ENV]
    if WORKER_ID_ENV in os.environ:
        labels[res.LABEL_WORKER_ID_IN_SLICE] = os.environ[WORKER_ID_ENV]
    return labels


def worker_chip_env(chips: Sequence[int], host_chips: int,
                    env: Dict[str, str]) -> Dict[str, str]:
    """Make ``env`` (a worker's environment, mutated and returned) open
    exactly the granted ``chips`` of a host that has ``host_chips``.

    A worker granted chips sees those and no others. A worker granted
    none is held to the CPU platform: a TPU chip belongs to one process
    at a time, so a data worker, controller or proxy that touched JAX
    first would take the chip from the worker that was granted it.

    To open ONE chip of a host that has more, libtpu needs, beyond
    TPU_VISIBLE_CHIPS, the chip described as a one-host slice of its own.
    With that, workers on different chips of one host run side by side;
    without it libtpu takes the whole-host lock and the second worker dies
    at backend init. Found on a four-chip v5e host (CHANGES.md, PR 21),
    where a PAIR of chips opens under no bounds tried (1,2,1 and 2,1,1,
    rows and columns, either spelling of the variables: 1x2 is no v5e
    topology), so a two-chip grant is left to libtpu, which refuses it. A
    whole-host grant keeps the machine's own bounds.
    """
    from ray_tpu._private.config import get_config

    visible = get_config().tpu_visible_chips_env
    if not chips:
        env["JAX_PLATFORMS"] = "cpu"
        env.pop(visible, None)
        return env
    env[visible] = ",".join(str(i) for i in chips)
    if len(chips) == 1 and host_chips > 1:
        env["TPU_CHIPS_PER_HOST_BOUNDS"] = "1,1,1"
        env["TPU_HOST_BOUNDS"] = "1,1,1"
    return env
