"""The chip benchmark: harness, yardstick and plain references.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that belongs to one
configuration, traffic mix or per-layer metric sits in a file of its own
(``configs/``, ``traffic/``, ``metrics/``), found by the name that
``BENCHMARK.json`` gives it.
"""
