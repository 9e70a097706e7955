"""Production mesh construction.

A *function*, not a module-level constant, so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device; only
``dryrun.py`` forces 512 host devices via XLA_FLAGS before first init).

Topology: TPU v5e pods of 256 chips as a (16, 16) torus.
  single-pod:  (16, 16)        axes ("data", "model")
  multi-pod:   (2, 16, 16)     axes ("pod", "data", "model")

DP spans ("pod", "data") — the pod axis carries only gradient
all-reduces (DCN-friendly); TP/EP stay inside a pod's ICI.

Serving replicas use :func:`make_serving_mesh` instead: a 1-D ``model``
axis over a *contiguous slice* of devices.  No ``data`` axis exists on a
serving mesh, so the FSDP rules (``embed_fsdp → "data"``) resolve to
replication and weights are TP-only resident — no per-layer all-gathers
on the prefill/decode path (DESIGN.md §15).  The cluster hands each
replica its own slice, composing DP replicas × TP shards.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # GSPMD propagation from the logical-axis rules needs Auto axes (the
    # jax.make_mesh default became Explicit)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_serving_mesh(devices: Optional[Sequence] = None, *, tp: int) -> Mesh:
    """TP-only mesh for one engine replica: ``tp`` devices on one
    ``"model"`` axis.

    ``devices`` is the replica's contiguous device slice (defaults to the
    first ``tp`` of ``jax.devices()``).  Passing more than ``tp`` devices
    is an error — a replica must never silently span another replica's
    slice.
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if devices is None:
        devices = jax.devices()[:tp]
    devices = list(devices)
    if len(devices) != tp:
        raise ValueError(
            f"serving mesh needs exactly tp={tp} devices, got {len(devices)}"
            + ("" if devices else " — force host devices via XLA_FLAGS="
               "--xla_force_host_platform_device_count=N")
        )
    return Mesh(np.asarray(devices, dtype=object).reshape(tp), ("model",))
