"""On-chip benchmark of DPS training: see BENCHMARK.json and PERF.md."""
