"""TPU device introspection: peak-FLOPs table for MFU accounting and
generation→topology metadata used by the platform's spawner config
(``web/jwa``: accelerator type + topology dropdowns)."""

from __future__ import annotations

import jax

# bf16 peak matmul TFLOP/s per chip (public spec sheets).
_PEAK_TFLOPS_BY_KIND = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,  # v5e
    "TPU v5e": 197.0,
    "TPU v5": 459.0,  # v5p
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,  # v6e / Trillium
    "TPU v6e": 918.0,
    "TPU v7": 4614.0,
}


def peak_flops_per_chip(device: jax.Device | None = None) -> float:
    """Peak bf16 FLOP/s for one chip. A ``device_kind`` that is not in
    the table (a CPU, a chip nobody entered) raises: a utilization
    against a peak of zero is not a number."""
    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind
    for name, tflops in _PEAK_TFLOPS_BY_KIND.items():
        if kind.startswith(name):
            return tflops * 1e12
    raise ValueError(
        f"no peak FLOP/s known for device_kind {kind!r} "
        f"(platform {device.platform!r}); known: "
        f"{sorted(_PEAK_TFLOPS_BY_KIND)}"
    )


# GKE scheduling metadata: accelerator-type string (the
# ``cloud.google.com/gke-tpu-accelerator`` nodeSelector value) →
# the topologies a user may request and chips-per-host. This drives the
# platform side: the notebook-controller turns (type, topology) into
# ``google.com/tpu`` limits + topology nodeSelectors, and multi-host
# topologies into StatefulSet replicas == host count.
TPU_TOPOLOGIES = {
    "tpu-v5-lite-podslice": {  # v5e
        "chips_per_host": 4,
        "topologies": ["1x1", "2x2", "2x4", "4x4", "4x8", "8x8", "8x16", "16x16"],
    },
    "tpu-v5p-slice": {
        "chips_per_host": 4,
        "topologies": ["2x2x1", "2x2x2", "2x4x4", "4x4x4", "4x4x8", "8x8x8"],
    },
    "tpu-v6e-slice": {
        "chips_per_host": 4,
        "topologies": ["1x1", "2x2", "2x4", "4x4", "4x8", "8x8", "8x16", "16x16"],
    },
}


def chips_in_topology(topology: str) -> int:
    n = 1
    for part in topology.split("x"):
        n *= int(part)
    return n


def hosts_in_slice(accelerator_type: str, topology: str) -> int:
    meta = TPU_TOPOLOGIES[accelerator_type]
    chips = chips_in_topology(topology)
    return max(1, chips // meta["chips_per_host"])
