"""The benchmark of the serving path: see bench/run.py and PERF.md."""
