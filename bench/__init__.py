"""The benchmark: ``python3 bench/run.py --workload <cell> ...`` runs one
cell of ``BENCHMARK.json`` once.  See ``bench/harness.py``."""
