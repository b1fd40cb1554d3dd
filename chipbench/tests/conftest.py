"""chipbench's own tests: CPU, tiny sizes. Nothing here is a chip run, and
no number these tests read is a device metric."""

import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
TINY_ROWS = 16_000
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
# Cells for the tests alone: every analyzer family the yardstick knows (HLL,
# quantiles, correlation, predicates, ``where``; the grouping analyzers) on a
# 23-column table, through the loops of the two real cells. Cells that a
# later PR brings as data files lean on this part of the reference.
RICH = {"rich.scan": ("profile10m.scan", "rich_scan"),
        "rich.serial": ("append1b.serial", "rich_stream"),
        "rich.grouping": ("profile10m.scan", "rich_grouping")}


@pytest.fixture
def benchmark_copy(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under ``paths``."""
    import shutil

    root = tmp_path / "copy"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


@pytest.fixture
def tiny_cell():
    """A cell of BENCHMARK.json with its configuration cut to tiny rows."""
    from chipbench import cells

    def fixture(name):
        import json

        with open(os.path.join(FIXTURES, name + ".json")) as f:
            return json.load(f)

    def load(name, rows=TINY_ROWS, root=cells.ROOT):
        base, suite = RICH.get(name, (name, None))
        cell = copy.deepcopy(cells.load_cell(base, root))
        if suite:
            partitioned = "partition_rows" in cell["config"]
            cell["config"], cell["suite"] = fixture("rich23"), fixture(suite)
            if partitioned:
                cell["config"]["partition_rows"] = 1
        cell["config"]["rows"] = rows
        if "partition_rows" in cell["config"]:
            cell["config"]["partition_rows"] = rows // 8
        return cell

    return load


@pytest.fixture
def run_tiny(tiny_cell):
    """Drives everything after the look for a chip (``run.run_cell``)."""
    import time

    import deequ_tpu  # noqa: F401
    from chipbench import run

    def go(name, seed=7, seconds=0.3, trace=False, **kw):
        cell = tiny_cell(name, **kw)
        return run.run_cell(cell, seed, seconds, trace, dict(FAKE_DEVICE),
                            t0=time.perf_counter())

    return go
