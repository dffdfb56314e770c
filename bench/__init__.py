"""The benchmark: one command runs one cell of BENCHMARK.json (see run.py)."""
