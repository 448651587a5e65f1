"""Chip benchmark of the eigensolver: one cell per run of ``run.py``.

Cells, configurations, generators and per-layer metrics are files of
their own (``workloads/``, ``configs/``, ``generators/``, ``metrics/``),
found by the names in the repository's ``BENCHMARK.json``. The yardstick
(the pencil generators, the reference arithmetic that decides
``correct``, the trace reduction and the table of peaks) lives here and
imports nothing of the program under test.
"""
