"""The benchmark: ``python bench/run.py --workload <name> ...`` (see run.py)."""
