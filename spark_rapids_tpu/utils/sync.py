"""Device execution fence.

Every honest wall-clock measurement (op-time metrics, chip_smoke.py's
walls) ends with a device->host readback of a value that depends on the
computation. The fence was written for a platform that no longer exists,
whose ``block_until_ready`` returned before execution finished; whether
``block_until_ready`` alone would do on the current machine (one TPU v5e
through ``chiprun``) is not measured, and a readback is a fence on any
backend.

``fence`` reads back ONE element per array — a few bytes of transfer, fully
ordered behind the producing computation, so the readback cannot complete
until the array's producer has executed. This is the engine's analog of the
reference's stream synchronize (Cuda.deviceSynchronize / stream sync points
that GpuMetric op-time semantics rely on, reference GpuExec.scala:41-178).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def fence(*values: Any) -> None:
    """Force execution of every jax array in the given pytrees.

    Dispatches a 1-element slice of each array, then pulls ALL slices in a
    single ``jax.device_get`` — one round trip total, where per-array
    readbacks would pay one each (the cost of a readback is not measured
    on the current machine).
    """
    tiny = []
    for leaf in jax.tree_util.tree_leaves(values):
        if isinstance(leaf, jax.Array) and leaf.size:
            tiny.append(jnp.ravel(leaf)[:1])
    if tiny:
        jax.device_get(tiny)
