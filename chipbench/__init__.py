"""Chip benchmark of the packed serving path: one cell per run.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration in ``configs/<config>.json``, its traffic mix in
``traffic/<traffic>.json`` and each per-layer metric's reader in
``metrics/<metric>.py``.  See ``harness.py`` for the order of a run.
"""
