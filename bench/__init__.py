"""On-chip benchmark of the DMTL-ELM fit (see BENCHMARK.json and PERF.md)."""
