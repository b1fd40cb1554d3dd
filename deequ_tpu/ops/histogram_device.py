"""Histogram / segment-fold kernel tier: scatter, one-hot-MXU, Pallas.

Every remaining compute risk in the engine has the same shape — XLA's
TPU ``scatter`` lowering: the selection kernel's three bincount passes
(``ops/select_device.py``), the grouping path's scatter-add bincounts
and segment reductions (``ops/segment.py``), and the HLL register fold
before round 5 fixed it. The fix-idiom is already proven in this repo:
``ops/hll.py`` replaced a scatter-max register fold (~20 ns/row on the
bench chip) with a blocked one-hot bf16 MXU matmul for ~10x. This
module generalizes that idiom into a routed KERNEL TIER every
histogram-shaped reduction shares:

- ``"scatter"`` — the XLA lowering the engine has always run
  (``zeros.at[seg].add(w)``): the baseline every other variant is
  hard-asserted bit-exact against;
- ``"onehot"`` — the factored blocked one-hot matmul: a segment id
  splits into (hi, lo) digits of a B-wide radix, and the counts matrix
  is ``one_hot(hi)^T @ one_hot(lo)`` accumulated over row blocks. On
  the MXU the planes ride bf16 (products are exactly 0/1); on CPU they
  ride f32 (bf16 is software-emulated there — measured 8x SLOWER than
  scatter, while the f32 sgemm form wins 5-8x on narrow keyspaces).
  Per-block accumulation is f32 (block <= 2^18 rows, so every count
  fits f32's 2^24 integer range exactly) folded into an integer
  accumulator per block — counts are EXACT at any total row count;
- ``"pallas"`` — a Mosaic kernel for keyspaces too wide for the
  one-hot planes to fit: grid over (segment blocks x row blocks), each
  step accumulates a compare-against-iota tile into its output block (a
  VPU formulation — no scatter, no sorted structure). The v5e compiler
  accepts it (tests/test_chip_compile_counts.py) and chip_smoke.py runs it on
  the chip, but no policy resolves to it: it costs O(n * num_segments)
  compares and has no measured width range yet (ROADMAP C2), so it is
  reachable only through the DEEQU_TPU_HIST_VARIANT force knob and
  runs interpret-mode on CPU backends (the tier-1 parity harness).

Routing is a PLAN decision, not a call-site decision: the planner
(``ops/scan_plan.py``) resolves a ``hist_variant`` per scan attempt via
``ops/device_policy.resolve_hist_variant`` (keyspace width / row count
/ platform / force knob) and binds it around the traced update via
:func:`active_hist_variant`; host-driven kernels (``ops/segment.py``)
resolve per dispatch through the same policy fn. ``bincount`` reads the
ambient variant so traced code never threads variant arguments — the
traced-program caches stay correct because every consumer keys its
program on the resolved variant. The static twin of the routing is the
``plan-hist-scatter`` lint rule (deequ_tpu/lint/plan_lint.py): a plan
claiming a matmul/pallas hist variant must trace to a jaxpr with ZERO
``scatter-add`` primitives.

Exactness contract (docs/kernels.md): all three variants produce
IDENTICAL integer histograms — one-hot products are 0/1 in either
plane dtype, per-block f32 accumulation is exact below 2^24, and the
cross-block fold is integer addition. The ``kernelv`` tier-1 suite
pins parity against ``np.bincount`` across dtypes, widths, block
boundaries, and null slots.
"""

from __future__ import annotations

import contextvars
from contextlib import contextmanager
from functools import lru_cache
from typing import Optional

import numpy as np

#: the variants a histogram dispatch can resolve to (order = preference
#: order for documentation; resolution lives in device_policy)
HIST_VARIANTS = ("scatter", "onehot", "pallas")

#: one-hot radix width: 128 matches both the MXU/VPU lane count and the
#: CPU sgemm sweet spot measured in round 14 (B > 128 only widens the
#: matmul without narrowing the hi plane)
_ONEHOT_LANES = 128

#: row-block sizing: planes are (block, A) + (block, B) elements; the
#: budget caps their footprint (~128MB f32 at 2^25 elements) so a
#: vmapped consumer (the batched selection kernel) stays inside HBM,
#: while the floor keeps each matmul big enough to amortize dispatch
_ONEHOT_PLANE_BUDGET = 1 << 25
_ONEHOT_MAX_BLOCK = 1 << 18
_ONEHOT_MIN_BLOCK = 1 << 12

# -- active-variant seam ------------------------------------------------------

#: ambient variant for traced histogram calls. A ContextVar (not a bare
#: module global): serve workers trace programs from their own threads,
#: and a variant bound for one attempt must never leak into another
#: thread's trace.
_ACTIVE_VARIANT: contextvars.ContextVar = contextvars.ContextVar(
    "deequ_tpu_hist_variant", default="scatter"
)


def current_hist_variant() -> str:
    """The variant ambient histogram calls resolve to ("scatter" unless
    a planner bound one — see :func:`active_hist_variant`)."""
    return _ACTIVE_VARIANT.get()


@contextmanager
def active_hist_variant(variant: str):
    """Bind the ambient histogram variant for the duration of a traced
    update call (the planner wraps resolved select updates with this, so
    the binding is live exactly while THAT op's portion of the program
    traces — never at dispatch time, where it would be dead weight)."""
    if variant not in HIST_VARIANTS:
        raise ValueError(
            f"hist variant must be one of {HIST_VARIANTS}, got {variant!r}"
        )
    token = _ACTIVE_VARIANT.set(variant)
    try:
        yield
    finally:
        _ACTIVE_VARIANT.reset(token)


def pallas_available() -> bool:
    """True when jax ships the Pallas frontend this process can trace
    (CPU backends run it interpret-mode). Not a statement about Mosaic
    accepting a lowered kernel: a refusal surfaces at compile time as a
    typed ``DeviceCompileException`` (exceptions.classify_device_error)."""
    try:
        from jax.experimental import pallas  # noqa: F401
    # deequ-lint: ignore[bare-except] -- availability probe: absence of the pallas frontend IS the answer
    except Exception:  # noqa: BLE001 — jax built without pallas
        return False
    return True


# -- kernels ------------------------------------------------------------------


def _onehot_geometry(num_segments: int):
    """(A, B, block): hi/lo radix split + row block for one keyspace."""
    B = min(_ONEHOT_LANES, max(8, int(num_segments)))
    A = (int(num_segments) + B - 1) // B
    block = max(
        _ONEHOT_MIN_BLOCK,
        min(_ONEHOT_MAX_BLOCK, _ONEHOT_PLANE_BUDGET // (A + B)),
    )
    return A, B, block


def _plane_dtype(xp):
    """One-hot plane dtype: bf16 rides the MXU on accelerators; CPU
    backends keep f32 (bf16 is software-emulated there — measured ~8x
    slower than the f32 sgemm it replaces). Products are exactly 0/1
    either way, so the choice is pure speed, never accuracy."""
    import jax

    if jax.default_backend() == "cpu":
        return xp.float32
    return xp.bfloat16


def map_under_vmap(fn):
    """``fn`` with its OWN batching rule: under ``vmap`` the UNBATCHED
    program is mapped over the batch (``lax.map``) instead of each of its
    ops being batched.

    Every program built on the one-hot matmul needs this: XLA:TPU (libtpu
    0.0.34, v5e) MISCOMPILES the batched form — ``vmap`` turns the matmul
    into a dot_general with a batch dimension whose one-hot compares fuse
    into the convolution operands, and at batch 8 the first half of the
    batch comes back ALL ZERO, for every formulation tried
    (matmul/einsum/dot_general, bf16 or f32 planes; batches 2 and 32 were
    right; measured on the chip, PR 21 — the coalesced service answered
    ApproxCountDistinct = 0 for 4 of 8 tenants). The unbatched program is
    right. The rule wraps the WHOLE blocked program, not each block's
    matmul: one loop per call, not one per block (hundreds of loops
    quadrupled the fused step's compile time on the chip)."""
    import jax
    import jax.numpy as jnp

    mapped = jax.custom_batching.custom_vmap(fn)

    @mapped.def_vmap
    def _map_unbatched(axis_size, in_batched, *args):
        args = tuple(
            a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, batched in zip(args, in_batched)
        )
        out = jax.lax.map(lambda member: mapped(*member), args)
        return out, jax.tree.map(lambda _: True, out)

    return mapped


def onehot_counts(hi, a_width: int, lo, b_width: int, plane, weights=None):
    """``counts[a, b]`` = the (integer-weighted) number of rows with
    ``hi == a`` and ``lo == b``, as ``one_hot(hi)^T @ one_hot(lo)`` on the
    MXU with f32 accumulation — the shared core of the one-hot histogram
    tier and the HLL register fold (ops/hll.py). Ids outside their width
    one-hot to a zero row and are dropped. NOT safe to batch: callers
    reach it through a :func:`map_under_vmap` program."""
    import jax
    import jax.numpy as jnp

    oh = jax.nn.one_hot(hi, a_width, dtype=plane)
    ol = jax.nn.one_hot(lo, b_width, dtype=plane)
    if weights is not None:
        # the weighted lo plane rides f32 regardless of backend: a bf16
        # plane would round integer weights above 256 and break the
        # exact-counts contract (the hi plane stays 0/1, so only this
        # operand widens; the matmul promotes to f32)
        ol = ol.astype(jnp.float32) * weights.astype(jnp.float32)[:, None]
    return jnp.matmul(oh.T, ol, preferred_element_type=jnp.float32)


@lru_cache(maxsize=None)
def _bincount_onehot_fn(num_segments: int, plane: str, dtype: str,
                        weighted: bool):
    """The blocked one-hot bincount for one static signature, as a
    :func:`map_under_vmap` program of (seg[, weights])."""
    import jax.numpy as jnp

    A, B, block = _onehot_geometry(num_segments)

    def counts_of(seg, *w):
        seg = seg.astype(jnp.int32)
        counts = jnp.zeros((A, B), dtype=dtype)
        for s in range(0, seg.shape[0], block):
            sb = seg[s:s + block]
            hi = sb // B  # floor division: negatives land < 0 -> zero row
            lo = sb - hi * B
            counts = counts + onehot_counts(
                hi, A, lo, B, plane,
                weights=w[0][s:s + block] if w else None,
            ).astype(dtype)
        return counts.reshape(-1)[:num_segments]

    return map_under_vmap(counts_of)


def bincount_onehot(seg, num_segments: int, xp, weights=None, dtype=None):
    """Bincount (or integer-weighted segment sum) as a blocked factored
    one-hot matmul — the ops/hll.py MXU idiom generalized.

    ``seg`` is an (n,) integer array; counts cover ``[0, num_segments)``
    with out-of-range ids (negative sentinels, the trailing invalid
    slot a caller did not allocate) DROPPED — exactly the scatter
    path's semantics. Exactness: per-block f32 accumulation never
    exceeds the block row count (< 2^24), and blocks fold in integer
    arithmetic; with ``weights`` the caller must keep per-segment
    per-block totals below 2^24 (the engine only ever folds ones)."""
    fn = _bincount_onehot_fn(
        int(num_segments), np.dtype(_plane_dtype(xp)).name,
        np.dtype(dtype or xp.int32).name, weights is not None,
    )
    return fn(seg) if weights is None else fn(seg, weights)


# pallas tile geometry: a row block is ONE (8, 128) i32 tile of segment
# ids and the output block is (seg_block, 128) per-lane partial counts —
# every block's last two dims are multiples of the (8, 128) TPU tile,
# which the Mosaic lowering requires; interpret mode (CPU) accepts them
# regardless
_PALLAS_SEG_BLOCK = 512
_PALLAS_SUBLANES = 8
_PALLAS_LANES = 128
_PALLAS_ROW_BLOCK = _PALLAS_SUBLANES * _PALLAS_LANES


def bincount_pallas(
    seg,
    num_segments: int,
    xp,
    weights=None,
    dtype=None,
    interpret: Optional[bool] = None,
):
    """Bincount as a Pallas grid kernel: grid (segment blocks, row
    blocks), each step comparing one (8, 128) tile of segment ids against
    a (seg_block, 128) iota and accumulating the hits into its output
    block — O(n * num_segments) VPU compares with NO scatter and no
    sorted structure, the formulation for keyspaces too wide for the
    one-hot planes. The kernel keeps counts PER LANE (no cross-lane
    reduction or transpose inside Mosaic); XLA sums the 128 lanes after
    the call. ``interpret`` defaults to True off-TPU (the tier-1 parity
    harness) and is never taken on a TPU backend."""
    import jax
    from jax.experimental import pallas as pl

    dtype = dtype or xp.int32
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = seg.shape[0]
    seg = seg.astype(xp.int32)
    w = None if weights is None else weights.astype(xp.int32)
    nrb = max(1, (n + _PALLAS_ROW_BLOCK - 1) // _PALLAS_ROW_BLOCK)
    pad = nrb * _PALLAS_ROW_BLOCK - n
    if pad:
        # -1 matches no segment id: padding rows are dropped like any
        # other out-of-range sentinel
        seg = xp.concatenate([seg, xp.full((pad,), -1, xp.int32)])
        if w is not None:
            w = xp.concatenate([w, xp.zeros((pad,), xp.int32)])
    nsb = (num_segments + _PALLAS_SEG_BLOCK - 1) // _PALLAS_SEG_BLOCK
    # index maps must yield i32: a Python 0 is an i64 under x64, which
    # Mosaic cannot legalize
    zero = np.int32(0)
    row_spec = pl.BlockSpec(
        (_PALLAS_SUBLANES, _PALLAS_LANES), lambda j, k: (k, zero)
    )
    args = [seg.reshape(nrb * _PALLAS_SUBLANES, _PALLAS_LANES)]
    in_specs = [row_spec]
    if w is not None:
        args.append(w.reshape(nrb * _PALLAS_SUBLANES, _PALLAS_LANES))
        in_specs.append(row_spec)

    def kernel(seg_ref, *rest):
        w_ref, out_ref = (
            (rest[0], rest[1]) if len(rest) == 2 else (None, rest[0])
        )

        @pl.when(pl.program_id(1) == 0)
        def _():
            out_ref[...] = xp.zeros_like(out_ref)

        # TPU iota must be >= 2D (pallas guide): segment ids down the
        # sublanes, broadcast across the lanes
        ids = pl.program_id(0) * _PALLAS_SEG_BLOCK + (
            jax.lax.broadcasted_iota(
                xp.int32, (_PALLAS_SEG_BLOCK, _PALLAS_LANES), 0
            )
        )
        acc = out_ref[...]
        for r in range(_PALLAS_SUBLANES):
            # (1, 128) ids broadcast down the sublanes against the iota
            hit = (seg_ref[r:r + 1, :] == ids).astype(xp.int32)
            if w_ref is not None:
                hit = hit * w_ref[r:r + 1, :]
            acc = acc + hit
        out_ref[...] = acc

    out = pl.pallas_call(
        kernel,
        grid=(nsb, nrb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (_PALLAS_SEG_BLOCK, _PALLAS_LANES), lambda j, k: (j, zero)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (nsb * _PALLAS_SEG_BLOCK, _PALLAS_LANES), xp.int32
        ),
        interpret=interpret,
    )(*args)
    # pin the accumulator dtype: jnp.sum promotes i32 to the default int
    # (i64 under x64); per-lane partials sum exactly in i32 whenever the
    # total does
    return out.sum(axis=1, dtype=xp.int32)[:num_segments].astype(dtype)


def bincount_scatter(seg, num_segments: int, xp, weights=None, dtype=None):
    """The XLA scatter-add lowering (baseline variant). The tier
    contract is explicit: ids outside [0, num_segments) are DROPPED,
    never wrapped — jax normalizes negative ``.at`` indices numpy-style
    before any out-of-bounds mode applies, so negatives are pre-mapped
    to an out-of-range sentinel that ``mode="drop"`` then discards
    (engine callers all pre-map invalid rows to an allocated trailing
    slot anyway; the sentinel only defends the contract). The
    unweighted form adds a scalar 1 rather than an all-ones operand
    (measured ~2x faster on CPU — the historical select-kernel
    formulation)."""
    dtype = dtype or xp.int32
    zeros = xp.zeros((num_segments,), dtype=dtype)
    safe = xp.where(seg < 0, num_segments, seg)
    if weights is None:
        return zeros.at[safe].add(1, mode="drop")
    return zeros.at[safe].add(weights.astype(dtype), mode="drop")


def _scoped(variant: str, kernel):
    """``kernel`` under ``jax.named_scope("deequ.bincount.<variant>")``:
    the name a device trace gives the XLA ops of that variant (metadata
    only)."""
    import jax

    def scoped(seg, num_segments, xp, weights=None, dtype=None):
        with jax.named_scope("deequ.bincount." + variant):
            return kernel(seg, num_segments, xp, weights=weights,
                          dtype=dtype)

    return scoped


_KERNELS = {
    "scatter": _scoped("scatter", bincount_scatter),
    "onehot": _scoped("onehot", bincount_onehot),
    "pallas": _scoped("pallas", bincount_pallas),
}


def bincount_variant(
    variant: str, seg, num_segments: int, xp, weights=None, dtype=None
):
    """Histogram under an EXPLICIT variant — the host-driven kernels
    (ops/segment.py) resolve per dispatch via device_policy and key
    their jit caches on the resolved variant, so the ambient-binding
    seam (which exists for PLAN-routed traced code) would be dead
    weight there."""
    if variant not in HIST_VARIANTS:
        raise ValueError(
            f"hist variant must be one of {HIST_VARIANTS}, got {variant!r}"
        )
    return _KERNELS[variant](
        seg, num_segments, xp, weights=weights, dtype=dtype
    )


def bincount(seg, num_segments: int, xp, weights=None, dtype=None):
    """Histogram of integer segment ids under the AMBIENT variant
    (:func:`current_hist_variant`; "scatter" unless a planner bound one).
    All variants share one contract: counts over ``[0, num_segments)``,
    out-of-range ids dropped, exact integer results. Host numpy callers
    always take ``np.bincount`` — the variants are device formulations
    and the host path is already the latency-regime answer."""
    if xp is np:
        slots = np.where(
            (seg >= 0) & (seg < num_segments), seg, num_segments
        )
        if weights is None:
            counts = np.bincount(slots, minlength=num_segments + 1)
        else:
            # np.bincount's weighted form accumulates float64 — exact
            # for the small integer weights this tier admits; cast back
            counts = np.bincount(
                slots, weights=weights, minlength=num_segments + 1
            )
        return counts[:num_segments].astype(dtype or np.int64)
    return _KERNELS[current_hist_variant()](
        seg, num_segments, xp, weights=weights, dtype=dtype
    )
