"""The chip benchmark of the replica store.

``benchmark/run.py`` runs one cell of ``BENCHMARK.json``.  Everything a
cell needs is found by name: its deployment in ``configs/``, its traffic
mix in ``traffic/`` (a data file naming the loop in ``drivers/`` that
drives it), and each per-layer metric in ``metrics/``.
"""
