"""Compile guards of the selection kernel, the HLL register folds, the
serving tenant step and the windowed pane step — compiled for a
described v5e (tests/chip_compile.py says how)."""

import re

import numpy as np

from chip_compile import (  # noqa: F401 — topo, one_chip, as_tpu: fixtures
    SMOKE_ROWS, _aval, _compile, as_tpu, chip_smoke, one_chip, topo,
)


def test_hll_mxu_fold_compiles(as_tpu, one_chip):
    import jax.numpy as jnp

    from deequ_tpu.ops import hll

    def fold(idx, rank, valid):
        return hll.registers_from_idx_rank(idx, rank, valid, 9, jnp)

    import jax

    lowered = jax.jit(fold).lower(
        _aval((SMOKE_ROWS,), np.int32, one_chip),
        _aval((SMOKE_ROWS,), np.int32, one_chip),
        _aval((SMOKE_ROWS,), np.bool_, one_chip),
    )
    # the MXU branch (one-hot bf16 matmul), not the scatter segment_max
    assert "bf16" in lowered.as_text()
    text = lowered.compile().as_text()
    assert "convolution" in text and "scatter" not in text


def test_vmapped_hll_fold_compiles_without_a_batched_convolution(
    as_tpu, one_chip
):
    """The tenant-axis form of the fold (serving, 8 members): it must lower
    to the UNBATCHED one-hot convolution inside a loop — XLA:TPU returned
    zeros for half of a batch-8 convolution on the chip (PR 21)."""
    import jax
    import jax.numpy as jnp

    from deequ_tpu.ops import hll

    K, n = 8, chip_smoke.SERVE_ROWS
    compiled = _compile(
        jax.vmap(lambda i, r: hll._registers_mxu_fold(i, r, 512, jnp)),
        _aval((K, n), np.int32, one_chip),
        _aval((K, n), np.int32, one_chip),
    )
    text = compiled.as_text()
    assert "convolution" in text and "while" in text


def test_selection_kernel_compiles_at_config3_width(as_tpu, one_chip):
    """BASELINE config 3: 50 quantile columns (k=256) over one resident
    chunk of the benchmark's cell ``quantiles12m50.qscan`` (2 GiB over 450
    bytes a row: a row count no block divides), every histogram pass on
    the one-hot tier as the chip routes, and the step's temporaries small
    enough to stand beside the cell's 5.63 GB resident table."""
    import jax.numpy as jnp

    from deequ_tpu.ops.device_policy import resolve_hist_variant
    from deequ_tpu.ops.histogram_device import active_hist_variant
    from deequ_tpu.ops.select_device import chunk_summary_select_batched

    K, n, k = 50, (2 << 30) // 450, 256
    variant = resolve_hist_variant((1 << 16, (k + 2) * 256 + 1), rows=n)
    assert variant == "onehot"

    def summaries(x, valid, lo):
        with active_hist_variant(variant):
            return chunk_summary_select_batched(x, valid, k, n, jnp, lo=lo)

    compiled = _compile(
        summaries,
        _aval((K, n), np.float32, one_chip),
        _aval((K, n), np.bool_, one_chip),
        _aval((K, n), np.float32, one_chip),
    )
    # a sort INSTRUCTION, not the word: the module's text also lists the
    # call stack of whoever first traced the cached bincount program (a
    # test named "..._sort_reference_..." when it shares this worker)
    text = compiled.as_text()
    assert not re.search(r"\bsort\(", text)
    # no walk over the n elements: the passes are matmuls, the compaction
    # gathers W slots
    assert "convolution" in text and not re.search(r"\bscatter\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
    # the remainder's slots by COUNTING (PERF.md section 6, PR 35): no
    # running count over the n elements anywhere in the step (the longest
    # is over the 4 x 37,283 words of a plane's 32-element groups) and no
    # loop of gathers in the extraction (a binary search a slot was 23
    # dependent gathers, 5.4 ms a column on the chip)
    windows = [
        line for line in text.splitlines() if re.search(r"\breduce-window\(", line)
    ]
    assert windows
    for line in windows:
        shape = re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1)
        assert np.prod([int(d) for d in shape.split(",") if d]) <= n // 16, line
    assert not [
        line for line in text.splitlines()
        if re.search(r"\bwhile\(", line) and "deequ.select.extract" in line
    ]
    # pass 1 is the program passes 2-3 are: its matmul fusion reads the
    # leading digits as an operand (the barrier held through the TPU
    # pipeline) and the compiler builds all three with one emitter. At 256
    # rows against an iota on both sides it picked another, four times
    # slower on the chip (PERF.md section 6, PR 31)
    computations, emitters = text.split("\n}\n"), {}
    for scope in ("pass1", "pass2", "pass3"):
        (computation,) = [
            c for c in computations
            if re.search(rf"convolution\(.*deequ\.select\.{scope}/", c)
        ]
        name = re.match(r"(%\S+) \(", computation.lstrip()).group(1)
        (call,) = [
            line for line in text.splitlines() if f"calls={name}," in line
        ]
        emitters[scope] = re.search(r'"emitter":"(\w+)"', call).group(1)
        if scope == "pass1":
            assert re.search(r"= s32\[\d+\]\S* parameter\(", computation)
    assert emitters["pass1"] == emitters["pass2"] == emitters["pass3"], emitters


def test_coalesced_tenant_step_compiles(topo, one_chip, monkeypatch):
    """The vmapped packed program of chip_smoke's serving phase: the plan
    is captured from the real service on a CPU run of tiny tenants, then
    its program is rebuilt and compiled at the smoke's (submits, rows)."""
    import dataclasses

    import jax

    from deequ_tpu.parallel.mesh import use_mesh
    from deequ_tpu.serve import VerificationService, executor

    captured = []
    real_build = executor._build_packed_program

    def capture(plan, lut_keys, op_order=None):
        captured.append((plan, lut_keys, op_order))
        return real_build(plan, lut_keys, op_order=op_order)

    rows, tenants = 4096, 4
    with monkeypatch.context() as capture_run, use_mesh(None):
        capture_run.setattr(executor, "_build_packed_program", capture)
        service = VerificationService(max_batch=tenants)
        try:
            futures = [
                service.submit(
                    chip_smoke._tenant_table(rows, 100 + t),
                    [chip_smoke._tenant_check(rows)], tenant=f"t{t}",
                )
                for t in range(tenants)
            ]
            for f in futures:
                f.result(timeout=120)
        finally:
            service.stop()
    assert captured, "the service never built a coalesced program"
    plan, lut_keys, op_order = captured[-1]
    assert not lut_keys  # numeric tenants: no dictionary LUT arguments
    layout = plan.layout
    # the satisfies predicate routed x over the exact wide-f64 plane
    assert "x" in layout["wide"]

    # the same plan at the smoke's row count, traced as the chip would
    K, n = chip_smoke.SERVE_SUBMITS, chip_smoke.SERVE_ROWS
    big = dataclasses.replace(
        plan, key=dataclasses.replace(plan.key, chunk=n)
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: list(topo.devices))
    _tree, _flat, vstep = real_build(big, lut_keys, op_order=op_order)
    avals = (
        _aval((K, len(layout["wide"]), n), np.float64, one_chip),
        _aval((K, len(layout["pair"]), n), np.float32, one_chip),
        _aval((K, len(layout["pair"]), n), np.float32, one_chip),
        _aval((K, len(layout["narrow_i32"]), n), np.int32, one_chip),
        _aval((K, len(layout["masked"]), n), np.bool_, one_chip),
        _aval((K, 0, n), np.int32, one_chip),
        _aval((K, n), np.bool_, one_chip),
        _aval((K, len(layout.get("enc", ())), n), np.int16, one_chip),
        {},
    )
    assert vstep.lower(*avals).compile() is not None


def test_pane_step_compiles_in_f64(one_chip):
    from deequ_tpu.analyzers import (
        Completeness, Maximum, Mean, Minimum, Size, Sum,
    )
    from deequ_tpu.windows.engine import (
        _data_columns, _make_step, pane_signature,
    )

    sig = pane_signature([
        Size(), Completeness("v"), Mean("v"), Minimum("v"), Maximum("v"),
        Sum("v"),
    ])
    data_cols = _data_columns(sig)
    n, panes = chip_smoke.WINDOW_BATCH_ROWS, 4
    compiled = _compile(
        _make_step(sig, 20.0, data_cols),
        _aval((n,), np.float64, one_chip),       # event times
        _aval((panes,), np.float64, one_chip),   # pane starts
        _aval((), np.float64, one_chip),         # watermark fence
        *[_aval((n,), np.float64, one_chip) for _ in data_cols],
        *[_aval((n,), np.bool_, one_chip) for _ in data_cols],
    )
    assert compiled is not None
