"""Chip benchmark of the serving stack: one cell, one run, one result line.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout.  Everything a cell needs is
found by name: ``BENCHMARK.json`` names the cell's configuration
(``configs/<name>.json``) and traffic mix (``traffic/<name>.json``), and
every metric is a reader in ``metrics/<name>.py``.
"""
