"""Device-side quantile sketching: per-chunk sort + deterministic
strata compaction, folded into the standard KLL merge algebra.

The reference builds its KLL sketch inside the engine's parallel partitions
(mapPartitions + treeReduce, analyzers/runners/KLLRunner.scala:104-177);
the per-row update loop is the hot path. The TPU-first equivalent avoids
per-row updates entirely:

  1. On device, sort the chunk's valid values (one XLA sort — the MXU-era
     analogue of the compactor's buffer sort, amortized over the whole
     chunk at once).
  2. Compact deterministically: choose level L = ceil(log2(ceil(m/k))) so
     the chunk reduces to at most k strata items of weight w = 2^L (each
     item is its stratum's MIDPOINT — rank error <= w/2 per item,
     deterministic, no sampling variance) plus < w exact remainder items
     at level 0. Total weight is exactly m.
  3. Fetch only the tiny summary (k + W items) and fold it into a host
     ``KLLSketchState`` whose compactors/merge/serde are unchanged — so
     device-built sketches merge with host-built and persisted ones
     (incremental compute keeps working).

Because the summary construction is a pure function of the sorted chunk,
it fuses into the SAME compiled pass as every other scan-shareable
analyzer: quantiles no longer cost an extra pass over the data (better
than the reference, which runs KLL as its own job).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import numpy as np

from deequ_tpu.ops.kll import KLLSketchState


def strata_capacity(local_n: int, sketch_size: int) -> int:
    """Static bound W on the remainder size: w = 2^ceil(log2(ceil(m/k)))
    <= W for every m <= local_n."""
    ratio = max((local_n + sketch_size - 1) // sketch_size, 1)
    return 1 << max(math.ceil(math.log2(ratio)), 0)


def strata_weight(m, k: int, xp):
    """(w, n_strata) for m valid rows and sketch size k — the stratum
    weight w = 2^L with L = ceil(log2(ceil(m/k))): the smallest power of
    two reducing m items to <= k strata. Computed with an INTEGER shift:
    XLA's float exp2/log2 are not exact at integer points (CPU x64
    exp2(3.0) = 7.999999999999998, truncating to w=7 — which silently
    dropped ~10% of rows on the single-device path until the mesh/no-mesh
    test matrix caught it). The epsilon guards log2 landing just above an
    integer; the where() doubles w if it still came out one step short,
    making w exact regardless of libm rounding. Shared by the sort-path
    summary (``chunk_summary``) and the selection kernel
    (ops/select_device.py) so their strata layouts can never drift."""
    ratio = xp.maximum((m + k - 1) // k, 1)
    log2r = xp.ceil(xp.log2(ratio.astype(xp.float64)) - 1e-9)
    w = xp.left_shift(xp.asarray(1, dtype=m.dtype), log2r.astype(m.dtype))
    w = xp.where(w * k < m, w * 2, w)
    return w, m // w


def chunk_summary(x, valid, sketch_size: int, local_n: int, xp, lo=None):
    """Inside-jit: one chunk/shard -> fixed-shape weighted summary.

    Returns {items (k+W,), weights (k+W,), count, min, max}; padding slots
    carry weight 0. Static shapes: k = sketch_size, W = strata_capacity.

    Two-float pair columns (``lo`` given, ops/df32.py): the sort runs on
    the f32 hi plane natively (f64 sorts are software-emulated on TPU) via
    argsort, the lo plane rides along through the same permutation, and
    f64 items are reconstructed only at the k+W gather points. Ties in hi
    order arbitrarily — the tied values differ by < 1 ulp(f32) relatively,
    far below the sketch's own rank error of w/2.
    """
    k = sketch_size
    W = strata_capacity(local_n, k)

    if lo is not None:
        from deequ_tpu.ops.df32 import masked_extremum

        xf32 = xp.where(valid, x, xp.asarray(np.float32(np.inf)))
        with jax.named_scope("deequ.sort.kll_summary"):
            order = xp.argsort(xf32)
        sx_hi = xf32[order]
        sx_lo = xp.where(valid, lo, xp.asarray(np.float32(0.0)))[order]

        def gather_items(idx):
            return sx_hi[idx].astype(xp.float64) + sx_lo[idx].astype(xp.float64)

        mn = masked_extremum(x, lo, valid, xp, "min")
        mx = masked_extremum(x, lo, valid, xp, "max")
    else:
        xf = xp.where(valid, x.astype(xp.float64), xp.inf)
        with jax.named_scope("deequ.sort.kll_summary"):
            sx = xp.sort(xf)

        def gather_items(idx):
            return sx[idx]

        mn = xp.min(xp.where(valid, x.astype(xp.float64), xp.inf))
        mx = xp.max(xp.where(valid, x.astype(xp.float64), -xp.inf))

    m = valid.sum()
    w, n_strata = strata_weight(m, k, xp)

    # strata midpoints: item i represents rows [i*w, (i+1)*w)
    sidx = xp.arange(k) * w + w // 2
    s_on = xp.arange(k) < n_strata
    items_s = gather_items(xp.clip(sidx, 0, local_n - 1))
    weights_s = xp.where(s_on, w, 0)

    # exact remainder (< w items) at level 0, preserving total weight == m
    ridx = n_strata * w + xp.arange(W)
    r_on = ridx < m
    items_r = gather_items(xp.clip(ridx, 0, local_n - 1))
    weights_r = xp.where(r_on, 1, 0)

    items = xp.concatenate([items_s, items_r])
    weights = xp.concatenate([weights_s, weights_r])
    # zero the padding values so gathered buffers are deterministic
    items = xp.where(weights > 0, items, 0.0)

    return {
        "items": items,
        "weights": weights.astype(xp.float64),
        "count": m,
        "min": mn,
        "max": mx,
    }


def chunk_summary_batched(X, M, sketch_size: int, local_n: int, xp, lo=None):
    """K columns at once: (K, n) values + (K, n) validity -> summaries with
    a leading K axis. One BATCHED device sort (vmap) instead of K
    independent sorts — XLA tiles the (K, n) sort far better than K
    separate sort ops, which is the dominant cost of wide quantile
    profiles (BASELINE config 3: ApproxQuantile over 50 columns)."""
    import jax

    if lo is not None:
        return jax.vmap(
            lambda x, v, l: chunk_summary(
                x, v, sketch_size, local_n, xp, lo=l
            )
        )(X, M, lo)
    return jax.vmap(
        lambda x, v: chunk_summary(x, v, sketch_size, local_n, xp)
    )(X, M)


def fold_summaries(
    items: np.ndarray,
    weights: np.ndarray,
    sketch_size: int,
    shrinking_factor: float,
) -> Optional[KLLSketchState]:
    """Host-side: gathered per-chunk summaries -> one KLLSketchState.

    Weights are exact powers of two; items of weight 2^l become level-l
    compactor entries, then one standard compaction bounds the size. The
    result obeys the normal KLL merge algebra (mergeable with host-built
    and persisted sketches)."""
    # deequ-lint: ignore[host-fetch] -- gathered summaries were drained (and fetch-accounted) before this host-side fold
    items = np.asarray(items, dtype=np.float64).ravel()
    # deequ-lint: ignore[host-fetch] -- gathered summaries were drained (and fetch-accounted) before this host-side fold
    weights = np.asarray(weights, dtype=np.float64).ravel()
    on = weights > 0
    if not on.any():
        return None
    items = items[on]
    levels = np.log2(weights[on]).astype(np.int64)
    max_level = int(levels.max())
    compactors = [
        np.sort(items[levels == l]) for l in range(max_level + 1)
    ]
    count = int(weights[on].sum())
    sketch = KLLSketchState(sketch_size, shrinking_factor, compactors, count)
    sketch._compress()
    return sketch
