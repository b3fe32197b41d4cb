"""Chip benchmark of GradsSharding rounds through ``FederatedSession``.

Entry point: ``chipbench/run.py``. Cells, metrics and bounds are listed in
``BENCHMARK.json`` at the root of the repository; each configuration,
traffic mix, codec reference and per-layer metric is a file of its own
under this directory, found by its name.
"""
