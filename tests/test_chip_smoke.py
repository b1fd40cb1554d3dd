"""chip_smoke.py rehearsed on the CPU: every phase at tiny sizes on the
virtual 8-device mesh, the ``--chips 4`` phase on 4 of the virtual devices,
the refusal to run without a TPU, the shape of the last line, and the
compile-cache placement rule (deequ_tpu/__init__.py).

Nothing here is a chip run: the device phase is faked where a test needs
to get past it, and no number these tests print is a device metric.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = {
    "RESIDENT_ROWS": 20_000,   # above HOST_GROUP_LIMIT: device grouping runs
    "WIDE_CARD": 2_000,
    "SERVE_TENANTS": 4,
    "SERVE_SUBMITS": 8,
    "SERVE_ROWS": 2_000,
    "WINDOW_STREAMS": 2,
    "WINDOW_BATCHES": 4,
    "WINDOW_BATCH_ROWS": 500,
    "KERNEL_ROWS": 1 << 14,
    # 3 sigma of the p=9 sketch: the 6% pin is for the smoke's own size and
    # seed; tiny tables keep the EXACT check against the host registers
    "HLL_REL_BOUND": 0.14,
}


def _fake_tpu(count):
    device = {"platform": "tpu", "kind": "rehearsal (cpu)", "count": count}

    def phase_device(want_count):
        assert want_count <= count
        return device

    return phase_device, device


def test_main_runs_every_phase_and_pins_the_last_line(monkeypatch, capsys):
    for name, value in TINY.items():
        monkeypatch.setattr(chip_smoke, name, value)
    phase_device, device = _fake_tpu(1)
    monkeypatch.setattr(chip_smoke, "phase_device", phase_device)
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    # the last line is exactly the contract's object, key order included
    assert lines[-1] == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "rehearsal (cpu)", "count": 1}}'
    )
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    facts = [json.loads(line) for line in lines[:-1]]
    phases = [f["phase"] for f in facts if "phase" in f]
    assert phases == [
        "round_trip", "kernel_tier", "data", "resident", "streaming",
        "serving", "windows",
    ]
    resident = next(f for f in facts if f.get("phase") == "resident")
    assert resident["second_run"]["programs_built"] == 0
    assert resident["resident_bytes"] > 0
    assert resident["first_run"]["device_select_passes"] >= 1
    streaming = next(f for f in facts if f.get("phase") == "streaming")
    assert streaming["batches"] >= 8
    # one traced program serves every batch AND both partition streams
    assert streaming["stream"]["programs_built"] == 1
    assert streaming["states"]["programs_built"] == 0
    serving = next(f for f in facts if f.get("phase") == "serving")
    assert serving["coalesced_tenants"] == TINY["SERVE_SUBMITS"]
    # on the CPU backend coalesced == serial bit for bit, floats included
    assert serving["float_metrics_not_bit_identical"] == 0


def test_chips4_phase_on_four_virtual_devices(monkeypatch, capsys):
    import jax

    monkeypatch.setattr(chip_smoke, "HLL_REL_BOUND", TINY["HLL_REL_BOUND"])
    chip_smoke.phase_sharded(
        TINY["RESIDENT_ROWS"], 21, jax.devices()[:4],
        wide_card=TINY["WIDE_CARD"],
    )
    fact = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert fact["phase"] == "sharded" and fact["metrics_equal"] is True
    assert fact["mesh_device_ids"] == [0, 1, 2, 3]
    shard_bytes = set(fact["per_device_shard_bytes"].values())
    assert len(fact["per_device_shard_bytes"]) == 4 and len(shard_bytes) == 1


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["one", "four"])
def test_main_refuses_to_run_without_a_tpu(argv, capsys):
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.main(argv)
    out = capsys.readouterr().out
    # the device is named first, and no result line follows
    assert json.loads(out.splitlines()[0])["device"]["platform"] == "cpu"
    assert '"ok"' not in out


def test_script_exits_nonzero_without_a_tpu():
    """As the driver runs it in the sandbox: another code than 0, no
    result line."""
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert done.returncode != 0
    assert '"ok"' not in done.stdout
    assert "no TPU" in done.stderr


def test_a_wrong_answer_fails_the_run(monkeypatch):
    """The checks compare against the numpy reference, not against
    themselves: a perturbed reference must raise."""
    monkeypatch.setattr(chip_smoke, "HLL_REL_BOUND", TINY["HLL_REL_BOUND"])
    table = chip_smoke.build_table(20_000, 21, n_numeric=6, wide_card=500)
    analyzers = chip_smoke.suite_analyzers(6)
    ref = chip_smoke.reference_metrics(table, analyzers)
    from deequ_tpu.analyzers.runner import AnalysisRunner

    metrics = AnalysisRunner.do_analysis_run(table, analyzers).metric_map
    chip_smoke.check_against_reference(metrics, ref, "healthy")
    size = next(a for a in analyzers if type(a).__name__ == "Size")
    ref[size] += 1.0
    with pytest.raises(chip_smoke.SmokeFailure, match="Size"):
        chip_smoke.check_against_reference(metrics, ref, "perturbed")


def test_degradation_events_fail_the_run():
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    chip_smoke.check_no_degradation()
    SCAN_STATS.record_degradation("cpu_fallback", reason="test")
    with pytest.raises(chip_smoke.SmokeFailure, match="cpu_fallback"):
        chip_smoke.check_no_degradation()


def test_compile_cache_rule(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, no directory is set in
    code; where it is not, ONE fixed directory inside the checkout."""
    import jax

    import deequ_tpu

    fixed = os.path.join(REPO, ".jax_cache")
    assert deequ_tpu._compile_cache_dir({}) == fixed
    assert deequ_tpu._compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    ) is None
    # this very process followed the rule at import
    expected = os.environ.get("JAX_COMPILATION_CACHE_DIR") or fixed
    assert jax.config.jax_compilation_cache_dir == expected
    # and a process started with the variable set keeps jax's own reading
    done = subprocess.run(
        [sys.executable, "-c",
         "import deequ_tpu, jax; print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
    )
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip().splitlines()[-1] == str(tmp_path)
