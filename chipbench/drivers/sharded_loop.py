"""``sharded_loop``: ``resident_loop``'s loop under a mesh of the cell's
chips. The table is ``persist()``ed row-sharded over
``Mesh(jax.devices()[:placement.devices], (placement.axis,))`` (the
configuration's ``placement``; never whatever else is visible), the
placement is proven before the first timed suite, and every
``VerificationSuite...run()`` goes through the sharded step."""

from __future__ import annotations

from chipbench.drivers import resident_loop
from chipbench.drivers.common import counters

slices = resident_loop.slices
rows_per_operation = resident_loop.rows_per_operation


class PlacementError(Exception):
    """The persisted table does not lie on the devices as the
    configuration's ``placement`` states."""


def prove_placement(cache, placement: dict) -> dict:
    """Holds the persisted table (the program's ``DeviceTableCache``) to
    ``placement``: it lies on exactly ``devices`` devices, every device
    holds ``1/devices`` of the bytes within ``share_tolerance``, and every
    chunk's ``row_valid`` is cut into ``devices`` equal shards of rows.
    Returns what it read; raises ``PlacementError`` otherwise."""
    n = placement["devices"]
    if cache is None or cache.mesh is None or cache.device_count != n:
        on = getattr(cache, "device_count", 0)
        raise PlacementError(f"persist() placed the table on {on} device(s), "
                             f"not row-sharded over {n}")
    held = {}
    for chunk in cache.device_chunks:
        for buf in chunk:
            for shard in buf.addressable_shards:
                device = int(shard.device.id)
                held[device] = held.get(device, 0) + int(shard.data.nbytes)
        rows = sorted(int(s.data.shape[0])
                      for s in chunk[6].addressable_shards)  # row_valid
        if rows != [cache.chunk // n] * n:
            raise PlacementError(
                f"row shards of a {cache.chunk}-row chunk over {n} devices: "
                f"{rows}")
    total = sum(held.values())
    if len(held) != n or any(
            abs(b / total - 1.0 / n) > placement["share_tolerance"]
            for b in held.values()):
        raise PlacementError(f"bytes by device, of {n} equal shares: {held}")
    return {"per_device_resident_bytes": [held[d] for d in sorted(held)],
            "chunks": len(cache.device_chunks), "chunk_rows": cache.chunk}


class Driver(resident_loop.Driver):
    def __init__(self, config: dict, traffic: dict, suite: dict, data: dict):
        super().__init__(config, traffic, suite, data)
        self.placement = config["placement"]
        self.mesh = None

    def _mesh(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        n = self.placement["devices"]
        devices = jax.devices()[:n]
        if len(devices) < n:
            raise PlacementError(f"{n} devices asked, {len(devices)} visible")
        return Mesh(np.array(devices), (self.placement["axis"],))

    def prepare(self) -> dict:
        from deequ_tpu.parallel.mesh import use_mesh

        self.mesh = self._mesh()
        before = counters()
        with use_mesh(self.mesh):
            phases = super().prepare()
        after = counters()
        phases.update(prove_placement(self.table._device_cache,
                                      self.placement))
        for seam in ("persist_pack", "persist_stage"):
            field = f"seam_{seam}_seconds"
            if field in after:
                phases[seam + "_s"] = after[field] - before.get(field, 0.0)
        return phases

    def window(self, window) -> None:
        from deequ_tpu.parallel.mesh import use_mesh

        with use_mesh(self.mesh):
            super().window(window)

    def release(self) -> None:
        from deequ_tpu.parallel.mesh import use_mesh

        with use_mesh(self.mesh):
            super().release()
