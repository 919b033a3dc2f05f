"""The benchmark: cells from BENCHMARK.json, driven by data files under this directory."""
