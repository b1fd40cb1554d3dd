"""What the staging planes of a host-packed chunk cost on THIS host, numpy
alone (no jax, no device): the readings behind the staging pool's two
constants in ``ops/scan_engine.py`` (PERF.md §6, PR 29).

    python benchmarks/staging_probe.py [--rows 1250000] [--cols 20]

(i)   the f64 -> (hi, lo) split of ``cols`` columns of ``rows`` rows into
      fresh planes (mapped, faulted and unmapped every time), into planes
      used before, into used planes that are views over a larger byte
      buffer, and the first touch of fresh planes with no arithmetic;
(ii)  transparent huge pages: the kernel's setting, numpy's own madvise
      switch, and how much of a fresh touched plane the kernel backed with
      huge pages;
(iii) for plane sizes from 4 kB to 64 MB, three planes at once as a chunk
      has them: allocate + fill + free against lock + fill of buffers that
      stay mapped; ascending (a young heap) and then descending (after
      large frees have raised glibc's dynamic mmap threshold).

Prints one JSON object; times are medians in milliseconds."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PAGE = 4096


def _ms(fn, reps):
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        fn()
        out.append(1000.0 * (time.perf_counter() - t))
    return statistics.median(out)


def _read(path):
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError as e:
        return f"unreadable: {e}"


def _anon_huge_kb():
    for line in _read("/proc/self/smaps_rollup").splitlines():
        if line.startswith("AnonHugePages:"):
            return int(line.split()[1])
    return None


def split_readings(rows, cols, reps):
    from deequ_tpu.ops.df32 import split_pair_np

    rng = np.random.default_rng(7)
    columns = [rng.normal(100.0 + i, 5.0, rows) for i in range(cols)]
    masks_src = [rng.random(rows) >= 0.01 for _ in range(cols)]

    def fill(hi, lo, masks):
        for i, x in enumerate(columns):
            split_pair_np(x, hi[i], lo[i])
            masks[i, :] = masks_src[i]

    def fresh():
        fill(np.empty((cols, rows), np.float32),
             np.empty((cols, rows), np.float32),
             np.empty((cols, rows), np.bool_))

    used = (np.empty((cols, rows), np.float32),
            np.empty((cols, rows), np.float32),
            np.empty((cols, rows), np.bool_))
    fill(*used)

    def view(nbytes, dtype):
        # a buffer an eighth larger than the plane, as a pool would hold
        buf = np.empty(nbytes + nbytes // 8, np.uint8)
        buf.fill(0)
        return buf[:nbytes].view(dtype).reshape(cols, rows)

    viewed = (view(4 * cols * rows, np.float32),
              view(4 * cols * rows, np.float32),
              view(cols * rows, np.bool_))

    def touch():
        for shape_dtype in (np.float32, np.float32, np.bool_):
            plane = np.empty((cols, rows), shape_dtype)
            flat = plane.reshape(-1).view(np.uint8)
            flat[::PAGE] = 0

    def map_unmap():
        for shape_dtype in (np.float32, np.float32, np.bool_):
            np.empty((cols, rows), shape_dtype)

    plane = np.empty((cols, rows), np.float32)
    plane.reshape(-1).view(np.uint8)[::PAGE] = 0

    def unmap_touched():
        nonlocal plane
        t = time.perf_counter()
        del plane
        dt = time.perf_counter() - t
        plane = np.empty((cols, rows), np.float32)
        plane.reshape(-1).view(np.uint8)[::PAGE] = 0
        return dt

    pages = (4 + 4 + 1) * cols * rows / PAGE
    touch_ms = _ms(touch, reps)
    return {
        "rows": rows, "cols": cols,
        "plane_mb": {"hi": 4e-6 * cols * rows, "lo": 4e-6 * cols * rows,
                     "masks": 1e-6 * cols * rows},
        "split_fresh_ms": _ms(fresh, reps),
        "split_reused_ms": _ms(lambda: fill(*used), reps),
        "split_reused_views_ms": _ms(lambda: fill(*viewed), reps),
        "touch_only_fresh_ms": touch_ms,
        "touch_us_per_4k_page": 1000.0 * touch_ms / pages,
        "map_unmap_untouched_ms": _ms(map_unmap, reps),
        "unmap_one_touched_100mb_plane_ms": 1000.0 * statistics.median(
            unmap_touched() for _ in range(reps)),
    }


def thp_readings():
    before = _anon_huge_kb()
    plane = np.empty(100 << 20, np.uint8)
    plane[::PAGE] = 1
    after = _anon_huge_kb()
    get = getattr(np._core.multiarray, "_get_madvise_hugepage", None)
    return {
        "enabled": _read("/sys/kernel/mm/transparent_hugepage/enabled"),
        "defrag": _read("/sys/kernel/mm/transparent_hugepage/defrag"),
        "numpy_madvise_hugepage": None if get is None else bool(get()),
        "anon_huge_kb_of_a_touched_100mb_plane": (
            None if None in (before, after) else after - before),
        "numpy": np.__version__,
    }


def size_cut(reps, planes=3):
    """``planes`` buffers of one size live at once, as a chunk's hi, lo and
    mask planes are: freed together they can lift the heap's top over
    glibc's trim threshold even where each is under its mmap threshold."""
    lock = threading.Lock()
    sizes = sorted([1 << k for k in range(12, 27)]
                   + [m << 20 for m in (20, 24, 28, 31, 33)])
    out = {}
    for order, seq in (("ascending", sizes), ("descending", sizes[::-1])):
        rows = []
        for nbytes in seq:
            kept = [np.empty(nbytes, np.uint8) for _ in range(planes)]
            for k in kept:
                k.fill(0)

            def fresh():
                held = [np.empty(nbytes, np.uint8) for _ in range(planes)]
                for h in held:
                    h.fill(1)

            def reused():
                for k in kept:
                    with lock:
                        pass
                    k.fill(1)
                for k in kept:
                    with lock:
                        pass

            n = max(reps, min(2000, (64 << 20) // nbytes))
            rows.append({"bytes": nbytes, "fresh_us": 1000.0 * _ms(fresh, n),
                         "reused_us": 1000.0 * _ms(reused, n)})
        out[order] = sorted(rows, key=lambda r: r["bytes"])
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rows", type=int, default=1_250_000)
    p.add_argument("--cols", type=int, default=20)
    p.add_argument("--reps", type=int, default=7)
    p.add_argument("--only-size-cut", action="store_true")
    args = p.parse_args(argv)
    result = {"cpus": os.cpu_count()}
    if not args.only_size_cut:
        result["thp"] = thp_readings()
        result["split"] = split_readings(args.rows, args.cols, args.reps)
    result["size_cut"] = size_cut(args.reps)
    print(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
