"""The chip benchmark: one command runs one cell once (``bench/run.py``).

``BENCHMARK.json`` at the root names the cells; each cell's
configuration, traffic mix and per-layer metric readers are files of
their own under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``.  Nothing here is imported by the program under test,
and the reference in ``bench/references`` imports nothing of it.
"""
