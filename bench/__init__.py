"""The on-chip benchmark: ``python3 bench/run.py --workload <name> ...``
runs one cell of ``BENCHMARK.json`` (see ``bench/harness.py``)."""
