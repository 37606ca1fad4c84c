"""On-chip benchmark: one cell (a model configuration under a traffic
mix) per run.  See ``bench/run.py`` and ``BENCHMARK.json``."""
