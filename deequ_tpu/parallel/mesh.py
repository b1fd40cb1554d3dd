"""Device mesh management for row-sharded analysis.

deequ's distribution contract (SURVEY.md §2.15) is: partitioned scan +
monoid state merge + shuffle group-by + tree reduce. The TPU-native
equivalent implemented here: rows are sharded over a 1-D ``jax.sharding.Mesh``
axis (``"rows"``), per-device partial states are computed inside
``shard_map``, and state merges ride ICI as XLA collectives
(psum / all_gather — see ops/scan_engine.py for the tagged merge).

Multi-host scaling: the same mesh spans hosts under ``jax.distributed``;
nothing in the engine distinguishes ICI from DCN — XLA routes collectives.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh

shard_map = jax.shard_map

ROW_AXIS = "rows"

_state = threading.local()


def default_mesh() -> Optional[Mesh]:
    """Mesh over all visible devices (None when single-device)."""
    devices = jax.devices()
    if len(devices) <= 1:
        return None
    import numpy as np

    # deequ-lint: ignore[host-fetch] -- array of device HANDLES for mesh construction, not array data
    return Mesh(np.array(devices), (ROW_AXIS,))


def current_mesh() -> Optional[Mesh]:
    """The mesh the scan engine should use for this thread.

    Resolution: explicitly set mesh (set_mesh/use_mesh) > default (all
    devices if more than one, else single-device execution).
    """
    explicit = getattr(_state, "mesh", "unset")
    if explicit != "unset":
        return explicit
    return default_mesh()


def set_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


def mesh_device_ids(mesh: Optional[Mesh]) -> tuple:
    """The ``.id`` of every device on the mesh, in mesh order (empty for
    the single-device/no-mesh case)."""
    if mesh is None:
        return ()
    return tuple(int(d.id) for d in mesh.devices.flat)


def mesh_excluding(mesh: Mesh, lost_ids) -> Optional[Mesh]:
    """The largest healthy sub-mesh: ``mesh`` minus the devices whose ids
    are in ``lost_ids``, preserving mesh order. Returns None when no
    device survives (the caller's cue that only the CPU fallback
    remains). A single survivor still gets a 1-device mesh — the scan
    must stay pinned to the HEALTHY chip, not drift to the runtime's
    default device (which may be the dead one)."""
    import numpy as np

    lost = {int(d) for d in lost_ids}
    survivors = [d for d in mesh.devices.flat if int(d.id) not in lost]
    if not survivors:
        return None
    # deequ-lint: ignore[host-fetch] -- array of device HANDLES for mesh construction, not array data
    return Mesh(np.array(survivors), tuple(mesh.axis_names))


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    prev = getattr(_state, "mesh", "unset")
    _state.mesh = mesh
    try:
        yield
    finally:
        if prev == "unset":
            del _state.mesh
        else:
            _state.mesh = prev
