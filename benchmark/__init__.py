"""The repo's benchmark: one cell of BENCHMARK.json per run (benchmark/run.py)."""
