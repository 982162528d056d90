"""On-chip benchmark of the KAKURENBO trainer (see BENCHMARK.json and PERF.md)."""
