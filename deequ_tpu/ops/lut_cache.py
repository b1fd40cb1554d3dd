"""Per-dictionary lookup-table memo.

String columns are dictionary-encoded; device string ops work by building a
host LUT over the dictionary (hashes, regex hits, lengths, type classes)
and gathering it by code on device. Those LUTs are built at TRACE time, so
any retrace (string programs are not globally cacheable — the LUT itself is
baked into the trace) used to redo O(cardinality) host work per run: for a
1M-entry dictionary that dominated wall time. The memo keys on the
dictionary array's identity (guarded by a weakref so a recycled id cannot
alias) plus a kind string naming the derivation.
"""

from __future__ import annotations

import weakref
from typing import Callable, Dict, Tuple

import numpy as np

_MAX_ENTRIES = 64
# (id(dictionary), kind) -> (weakref to dictionary, lut); insertion order
# doubles as LRU recency
_MEMO: Dict[Tuple[int, str], Tuple[weakref.ref, np.ndarray]] = {}
# same keying for device-resident LUTs (padded, transferred once)
_DEVICE_MEMO: Dict[Tuple[int, str, object], Tuple[weakref.ref, object]] = {}


def _next_pow2(n: int) -> int:
    return 1 << max(int(n - 1).bit_length(), 0) if n > 1 else 1


def pad_pow2(arr: np.ndarray) -> np.ndarray:
    """Pad a LUT to the next power-of-two length (stable shape buckets so
    jitted programs re-compile only when cardinality crosses a power of
    two, not on every dictionary size)."""
    n = max(len(arr), 1)
    target = _next_pow2(n)
    if len(arr) == target:
        return arr
    out = np.zeros(target, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def _mesh_value_key(mesh):
    """Meshes are keyed by VALUE (shape + axis names + device list), never
    by object identity: default_mesh() builds a fresh (equal) Mesh per run,
    and an id() key would both miss every run and risk aliasing a GC'd
    mesh's recycled id. With no mesh, the key carries the default-device
    override: the CPU-fallback path (scan_engine) runs under
    ``jax.default_device(cpu)``, and a memoized array COMMITTED to the
    accelerator must not be handed to a scan that is fleeing it."""
    if mesh is None:
        import jax

        default = getattr(jax.config, "jax_default_device", None)
        return None if default is None else ("default_device", str(default))
    return (mesh.devices.shape, tuple(mesh.axis_names), tuple(mesh.devices.flat))


def dictionary_lut_device(
    dictionary: np.ndarray,
    kind: str,
    builder: Callable[[np.ndarray], np.ndarray],
    mesh=None,
):
    """Device-resident, pow2-padded LUT, memoized per (dictionary identity,
    kind, mesh value): the array transfers to the device ONCE and is then
    passed to jitted scans as an argument — never baked into the trace as a
    megabyte constant, so string programs stay reusable and re-runs ship
    no dictionary bytes."""
    import jax

    key = (id(dictionary), kind, _mesh_value_key(mesh))
    entry = _DEVICE_MEMO.pop(key, None)
    if entry is not None and entry[0]() is dictionary:
        _DEVICE_MEMO[key] = entry
        return entry[1]
    host = pad_pow2(dictionary_lut(dictionary, kind, builder))
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        dev = jax.device_put(host, NamedSharding(mesh, PartitionSpec()))
    else:
        dev = jax.device_put(host)
    try:
        ref = weakref.ref(dictionary)
    except TypeError:
        return dev
    _DEVICE_MEMO[key] = (ref, dev)
    while len(_DEVICE_MEMO) > _MAX_ENTRIES:
        _DEVICE_MEMO.pop(next(iter(_DEVICE_MEMO)))
    return dev


def dictionary_lut(
    dictionary: np.ndarray,
    kind: str,
    builder: Callable[[np.ndarray], np.ndarray],
) -> np.ndarray:
    """Return builder(dictionary), memoized per (dictionary identity, kind)."""
    key = (id(dictionary), kind)
    entry = _MEMO.pop(key, None)
    if entry is not None and entry[0]() is dictionary:
        _MEMO[key] = entry  # re-insert: most recently used
        return entry[1]
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    SCAN_STATS.lut_builds += 1
    lut = builder(dictionary)
    try:
        ref = weakref.ref(dictionary)
    except TypeError:  # plain lists in tests; no identity guard possible
        return lut
    _MEMO[key] = (ref, lut)
    while len(_MEMO) > _MAX_ENTRIES:
        _MEMO.pop(next(iter(_MEMO)))
    return lut
