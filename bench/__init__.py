"""On-chip benchmark of the sweep engine: warm Monte-Carlo sweeps on a TPU.

``bench/run.py`` is the command; ``BENCHMARK.json`` at the checkout root
names the cells. Everything a cell needs is found by name: its fleet in
``configs/``, its traffic mix in ``traffic/``, the traffic's pieces in
``generators/`` and each per-layer metric's reader in ``metrics/``.
"""
