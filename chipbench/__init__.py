"""chipbench — the benchmark of deequ-tpu on the chip (see BENCHMARK.json, PERF.md)."""
