"""The pieces of a string column's Histogram and ApproxCountDistinct, each
ALONE on the chip, per dictionary class of ``strings12m`` (PERF.md section 6,
PR 32, is written from this):

    python benchmarks/bincount_probe.py [--rows 12500000] [--out <file>]

One column of ``--rows`` int32 codes per dictionary size (Zipf on the even
classes, uniform on the odd ones, as the cell's generator), resident on the
device; per formulation the median of five timed calls after one warm-up,
every count bit-equal to ``np.bincount``:

- ``scatter64`` / ``scatter32``: ``segment_sum`` of ones, int64 (what the
  grouping kernels ran until PR 32) against int32 slots and counts;
- ``onehot``: the factored one-hot matmul of ``ops/histogram_device.py``
  (only where ``resolve_hist_variant`` gives it: its row blocks are unrolled
  in Python, thousands of them past the cap);
- ``sort32``: ``jnp.sort`` of the codes alone, the floor of any sort-and-run-
  lengths formulation;
- ``topk64`` / ``topk32``: ``top_k(1000)`` over the dictionary's counts;
- ``gather``: the HLL LUT gather by code; ``fold``: the one-hot register fold
  of 12.5M rows; ``presence_fold``: the registers from the entries PRESENT
  (``counts > 0`` over the dictionary's slots, no per-row gather), as
  ``segment.resident_top_k`` folds them since PR 33
  (``hll.registers_from_present``: on the MXU whatever the dictionary).

Prints one JSON object. A time from a CPU run is not a device time: the
object names the platform it ran on."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CLASSES = (50_000, 250_000, 1_000_000, 3_000_000)


def _median_ms(fn, *args, runs: int = 5):
    import jax

    out = jax.block_until_ready(fn(*args))  # compile + warm-up
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1000.0 * (time.perf_counter() - t0))
    return statistics.median(times), out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rows", type=int, default=12_500_000)
    parser.add_argument("--seed", type=int, default=2147483777)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deequ_tpu  # noqa: F401 — x64
    from chipbench.generators import string_table
    from deequ_tpu.ops import hll
    from deequ_tpu.ops.device_policy import resolve_hist_variant
    from deequ_tpu.ops.histogram_device import bincount_onehot
    from deequ_tpu.ops.lut_cache import pad_pow2

    n = args.rows
    p = hll.precision_from_relative_sd()
    params = {"n_string": len(CLASSES), "dictionary_sizes": list(CLASSES),
              "zipf_exponent": 1.0, "null_share": 0.01}
    data = string_table.generate(n, args.seed, params)
    device = jax.devices()[0]
    result = {"platform": device.platform, "kind": device.device_kind,
              "rows": n, "classes": {}}
    for column, card in zip(data["columns"], CLASSES):
        codes_np = column["codes"]
        want = np.bincount(codes_np + 1, minlength=card + 1)
        codes = jax.device_put(codes_np)
        k1 = card + 1
        row = {}

        def slots(c, dtype):  # slot 0 = null, as the grouping kernels
            return (c + 1).astype(dtype)

        for name, dtype in (("scatter64", jnp.int64), ("scatter32", jnp.int32)):
            fn = jax.jit(lambda c, d=dtype: jax.ops.segment_sum(
                jnp.ones(c.shape, d), slots(c, d), num_segments=k1))
            row[name], got = _median_ms(fn, codes)
            assert np.array_equal(np.asarray(got), want), name
        if resolve_hist_variant((k1,), rows=n) == "onehot":
            fn = jax.jit(lambda c: bincount_onehot(
                slots(c, jnp.int32), k1, jnp, dtype=jnp.int32))
            row["onehot"], got = _median_ms(fn, codes)
            assert np.array_equal(np.asarray(got), want), "onehot"
        row["sort32"], _ = _median_ms(jax.jit(jnp.sort), codes)
        for name, dtype in (("topk64", np.int64), ("topk32", np.int32)):
            counts = jax.device_put(want.astype(dtype))
            row[name], got = _median_ms(
                jax.jit(lambda v: jax.lax.top_k(v, 1000)), counts)
            assert np.array_equal(np.asarray(got[0]),
                                  np.sort(want)[::-1][:1000]), name
        lut_np = hll.string_idx_rank_lut(column["dictionary"], p)
        lut = jax.device_put(pad_pow2(lut_np))
        gather = jax.jit(lambda t, c: t[jnp.maximum(c, 0)])
        row["gather"], packed = _median_ms(gather, lut, codes)

        def fold(packed, valid):
            return hll.registers_from_idx_rank(
                packed >> 6, packed & 0x3F, valid, p, jnp)

        row["fold"], regs = _median_ms(jax.jit(fold), packed, codes >= 0)
        present = jax.device_put(want[1:] > 0)
        row["presence_fold"], regs_present = _median_ms(
            jax.jit(lambda t, on: hll.registers_from_present(t, on, p, jnp)),
            jax.device_put(lut_np), present)
        assert np.array_equal(np.asarray(regs), np.asarray(regs_present))
        result["classes"][str(card)] = row
        print(f"bincount_probe: {card}: " + json.dumps(row), file=sys.stderr,
              flush=True)
    text = json.dumps(result, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
