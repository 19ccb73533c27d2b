"""The on-chip benchmark of cells: see ``chipbench/README.md`` and ``BENCHMARK.json``."""
