"""On-chip benchmark of the DRL x CFD trainer (see BENCHMARK.json)."""
