"""On-chip benchmark of the federated round: harness, yardstick and reference.

``bench/run.py`` is the one command. Everything that belongs to one model
configuration, one traffic mix, one per-layer metric or one cell's limits
sits in a file of its own under ``bench/configs``, ``bench/traffic``,
``bench/metrics`` and ``bench/limits``, found by the name that
``BENCHMARK.json`` gives it.
"""
