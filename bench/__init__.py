"""On-chip benchmark of the 4-bit optimizer system (see BENCHMARK.json)."""
