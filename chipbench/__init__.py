"""The chip benchmark: a harness driven by ``BENCHMARK.json`` and the data
files beside it. See ``chipbench/run.py``."""
