"""The command: refuses a CPU, refuses a bare directory, prints the
contract's last line; and with the timed path broken underneath,
``correct`` comes out false."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import cells, suite_build

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ARGS = ["--workload", "profile10m.scan", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def test_main_exits_nonzero_without_a_tpu_and_prints_no_result():
    bench = cells.load_benchmark()
    done = subprocess.run(bench["command"] + ARGS, cwd=cells.ROOT, env=ENV,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_a_directory_with_only_the_benchmark_refuses(benchmark_copy):
    env = {k: v for k, v in ENV.items() if k != "PYTHONPATH"}
    done = subprocess.run(cells.load_benchmark()["command"] + ARGS,
                          cwd=benchmark_copy,
                          env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_an_unknown_workload_is_refused():
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "nope", "--seed",
         "1", "--seconds", "1"], cwd=cells.ROOT, env=ENV, capture_output=True,
        text=True, timeout=120)
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_the_last_line_has_exactly_the_contracts_keys(run_tiny, capsys):
    result = run_tiny("profile10m.scan", seed=2**31 + 99)
    line = json.loads(json.dumps(result))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    # the counter metrics stand beside the rate in an untraced run too
    assert line["layer_counters"]["programs_built_in_window"] == 0
    assert line["layer_counters"]["host_ms_per_suite"] > 0
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    for check in line["checks"].values():
        assert set(check) == {"value", "limit"}
    traced = run_tiny("append1b.serial", trace=True)
    assert traced["correct"] is True
    # the traced operations come behind the window and count in nothing
    assert traced["attempted"] >= traced["window"]["operations"] + 3
    # a CPU trace has no device plane: no device number appears at all
    assert "busy_s" not in traced["device"] and "breakdown" not in traced
    assert "device_idle_pct" not in traced["metrics"]
    assert "scan_hbm_roofline" not in traced["metrics"]
    assert traced["metrics"]["programs_built_in_window"]["value"] == 0
    assert traced["metrics"]["packed_mb_per_s"]["value"] > 0


# -- the timed path broken underneath: correct must come out false -------------


def test_fault_state_returned_unchanged(run_tiny, monkeypatch):
    """An append that does not merge the running states (the step returns
    its state unchanged): every later cumulative answer is wrong."""
    from chipbench.drivers import append_loop

    real = append_loop.Driver._append

    def forgets(self, k):
        self._fresh_stores()
        return real(self, k)

    monkeypatch.setattr(append_loop.Driver, "_append", forgets)
    result = run_tiny("append1b.serial", seconds=0.5)
    assert result["correct"] is False
    assert result["checks"]["exact_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", ["profile10m.scan", "append1b.serial",
                                  "rich.scan", "rich.serial", "rich.grouping"])
def test_fault_half_of_the_rows_left_out(run_tiny, monkeypatch, cell):
    """The program sees every second row only, its means taken over the rest."""
    real = suite_build.table_of

    def half(data):
        from chipbench.columns import slice_rows

        return real(slice_rows(data, 0, data["rows"] // 2))

    monkeypatch.setattr(suite_build, "table_of", half)
    result = run_tiny(cell)
    assert result["correct"] is False
    assert result["checks"]["exact_mismatches"]["value"] > 0
    if cell != "rich.grouping":  # entropy of a random half: near equal
        assert result["checks"]["moment_rel"]["value"] > 1e-9


@pytest.mark.parametrize("cell,which,by", [
    (cell, which, by)
    for cell, kinds in (("profile10m.scan", 3), ("append1b.serial", 3),
                        ("rich.scan", 5), ("rich.serial", 5))
    for which, by in [("Mean", 1e-7), ("Maximum", 1e-12),
                      ("Completeness", 1e-7), ("ApproxCountDistinct", 1.0),
                      ("ApproxQuantile", 0.02)][:kinds]])
def test_fault_an_answer_altered_where_it_is_produced(
        run_tiny, monkeypatch, cell, which, by):
    real = suite_build.answers_of

    def altered(result, analyzers):
        out = real(result, analyzers)
        i = next(i for i, a in enumerate(analyzers)
                 if type(a).__name__ == which)
        out["values"][i] = out["values"][i] * (1.0 + by) if by < 1 else \
            out["values"][i] + by
        return out

    monkeypatch.setattr(suite_build, "answers_of", altered)
    assert run_tiny(cell)["correct"] is False


def test_fault_a_verdict_altered(run_tiny, monkeypatch):
    real = suite_build.answers_of

    def altered(result, analyzers):
        out = real(result, analyzers)
        out["verdict_rows"][0] = ("Success", "Success")
        return out

    monkeypatch.setattr(suite_build, "answers_of", altered)
    result = run_tiny("profile10m.scan")
    assert result["correct"] is False
    assert result["checks"]["verdict_mismatches"]["value"] >= 1


def test_fault_an_operation_that_raises(run_tiny, monkeypatch):
    from chipbench.drivers import resident_loop

    real = resident_loop.Driver._run
    calls = {"n": 0}

    def flaky(self, k=0):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("device lost")
        return real(self, k)

    monkeypatch.setattr(resident_loop.Driver, "_run", flaky)
    result = run_tiny("profile10m.scan")
    assert result["failed"] == 1 and result["correct"] is False
    assert result["attempted"] == result["window"]["operations"] + 1


@pytest.mark.parametrize("which", ["Uniqueness", "Histogram", "Entropy"])
def test_fault_a_grouping_answer_altered(run_tiny, monkeypatch, which):
    real = suite_build.answers_of

    def altered(result, analyzers):
        out = real(result, analyzers)
        i = next(i for i, a in enumerate(analyzers)
                 if type(a).__name__ == which)
        if which == "Histogram":
            first = next(iter(out["values"][i]))
            out["values"][i][first] += 1
        else:
            out["values"][i] *= 1.0 + 1e-7
        return out

    monkeypatch.setattr(suite_build, "answers_of", altered)
    assert run_tiny("rich.grouping", rows=24_000)["correct"] is False
