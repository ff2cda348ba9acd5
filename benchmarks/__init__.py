"""The benchmark: `run.py`, the harness, and the data files of its
configurations, traffic mixes and per-layer metrics (BENCHMARK.json)."""
