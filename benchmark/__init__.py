"""The benchmark: one command, cells found by name (see BENCHMARK.json, PERF.md)."""
