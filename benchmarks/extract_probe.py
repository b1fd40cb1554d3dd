"""The remainder's extraction of the selection kernel
(``ops/select_device._remainder_source``), its pieces each ALONE on the
chip, and the whole column summary against another checkout's, over one
resident chunk of the cell ``quantiles12m50.qscan`` (PERF.md section 6,
PR 35, is written from this):

    python benchmarks/extract_probe.py [--seed N] [--columns 50]
        [--parent <checkout>] [--out <file>]

The cell's table from ``--seed`` (its generator and configuration), chunk 0
of it (``(2 GiB) // 450`` rows a chunk, as ``persist()`` cuts it), the
first ``--columns`` columns as (hi, lo) float32 pairs on the device, every
histogram pass under the ``onehot`` variant as the chip's plan binds it.

- ``summary_ms``: ``chunk_summary_select`` of column 0, k = 256;
  ``parent_summary_ms`` the same from ``--parent``'s
  ``deequ_tpu/ops/select_device.py`` (loaded beside this checkout's, over
  this checkout's histogram tier);
- ``bit_equal``: over all ``--columns`` columns, ``items`` and ``weights``
  of both summaries byte for byte (``differing`` lists the columns that
  are not);
- the pieces, column 0: ``words`` (the one pass that packs the four 0/1
  planes), ``ties`` (the two index thresholds), ``slots`` (the first W set
  elements of the remainder's words: bincount, cumulative count, one
  gather a slot), ``source`` (all of it), ``values`` (the two W-element
  value gathers that follow).

Each time is the median of five rounds of twenty back-to-back calls, one
wait a round. Prints one JSON object. A time from a CPU run is not a
device time: the object names the platform it ran on."""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CHUNK_BYTES, ROW_BYTES, SKETCH = 2 << 30, 450, 256
CALLS = 20


def _median_ms(fn, *args, rounds: int = 5):
    import jax

    out = jax.block_until_ready(fn(*args))  # compile + warm-up
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            last = fn(*args)
        jax.block_until_ready(last)
        times.append(1000.0 * (time.perf_counter() - t0) / CALLS)
    return statistics.median(times), out


def _load_select_device(checkout: str):
    """``checkout``'s selection kernel as a module of its own name."""
    spec = importlib.util.spec_from_file_location(
        "parent_select_device",
        os.path.join(checkout, "deequ_tpu", "ops", "select_device.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=2147483777)
    parser.add_argument("--columns", type=int, default=50)
    parser.add_argument("--rows", type=int, default=None,
                        help="rows of the table (the cell's by default)")
    parser.add_argument("--parent", default=None)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deequ_tpu  # noqa: F401 — x64
    from chipbench.generators import profile_table
    from deequ_tpu.ops import select_device
    from deequ_tpu.ops.df32 import split_pair_np
    from deequ_tpu.ops.histogram_device import active_hist_variant
    from deequ_tpu.ops.kll_device import strata_capacity, strata_weight

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "quantiles12m50.json")) as f:
        config = json.load(f)
    rows = args.rows or config["rows"]
    n = min(CHUNK_BYTES // ROW_BYTES, rows)
    table = profile_table.generate(rows, args.seed, config["generator_params"])
    device = jax.devices()[0]
    columns = []
    for column in table["columns"][:args.columns]:
        hi, lo = split_pair_np(column["values"][:n])
        columns.append(tuple(
            jax.device_put(a, device) for a in (hi, column["mask"][:n], lo)
        ))
    del table

    def summary_of(module):
        def summary(x, valid, lo):
            with active_hist_variant("onehot"):
                return module.chunk_summary_select(
                    x, valid, SKETCH, n, jnp, lo=lo
                )
        return jax.jit(summary)

    out = {
        "platform": device.platform, "device_kind": device.device_kind,
        "seed": args.seed, "rows": n, "columns": len(columns),
        "sketch_size": SKETCH, "slots": strata_capacity(n, SKETCH),
    }
    summary = summary_of(select_device)
    out["summary_ms"], _ = _median_ms(summary, *columns[0])
    if args.parent:
        parent = summary_of(_load_select_device(args.parent))
        out["parent_summary_ms"], _ = _median_ms(parent, *columns[0])
        differing = []
        for index, column in enumerate(columns):
            ours, theirs = summary(*column), parent(*column)
            if any(
                np.asarray(ours[key]).tobytes()
                != np.asarray(theirs[key]).tobytes()
                for key in ("items", "weights", "count", "min", "max")
            ):
                differing.append(index)
        out["bit_equal"], out["differing"] = not differing, differing

    # the extraction's arguments for column 0, as chunk_summary_select has
    # them: the bounding keys by a host sort of the device's own keys
    x, valid, lo = columns[0]
    W = out["slots"]
    u = jax.jit(lambda x, valid: jnp.where(
        valid, select_device.monotone_u32(x, jnp),
        select_device.monotone_u32(jnp.float32(np.inf), jnp),
    ))(x, valid)
    ordered = np.sort(np.asarray(u))
    m = int(np.asarray(valid).sum())
    w, n_strata = strata_weight(np.int64(m), SKETCH, np)
    ranks = np.array([min(int(n_strata * w), m - 1), m - 1])
    bounds = ordered[ranks]
    tie_ranks = (ranks - np.searchsorted(ordered, bounds)).astype(np.int32)
    out["remainder"] = int(m - n_strata * w)
    bounds, tie_ranks = jnp.asarray(bounds), jnp.asarray(tie_ranks)

    def ties(words):
        first = select_device._first_index(words[0])
        live = select_device._low_lanes(n - first)
        return (
            select_device._nth_set(words[1] & live, tie_ranks[0] + 1, n),
            select_device._nth_set(words[3] & live, tie_ranks[1] + 1, n),
        )

    def slots(words):
        with active_hist_variant("onehot"):
            return select_device._first_set(words, W, n, jnp)

    def source(u):
        with active_hist_variant("onehot"):
            return select_device._remainder_source(
                u, bounds, tie_ranks, jnp.asarray(True), W, jnp
            )

    out["words_ms"], words = _median_ms(
        jax.jit(lambda u: select_device._bounds_words(u, bounds)), u
    )
    out["ties_ms"], _ = _median_ms(jax.jit(ties), words)
    # the elements strictly between the bounds: the remainder but its ties
    out["slots_ms"], _ = _median_ms(jax.jit(slots), words[0] & words[2])
    out["source_ms"], picked = _median_ms(jax.jit(source), u)
    out["values_ms"], _ = _median_ms(
        jax.jit(lambda x, lo, s: (x[s], lo[s])), x, lo, picked
    )

    text = json.dumps(out, indent=1, sort_keys=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
