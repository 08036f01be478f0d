"""The chip benchmark: one harness, driven by the data beside it.

``python3 -m bench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON line. Everything that belongs to one configuration, traffic mix or
per-layer metric is a file of its own, found by its name:
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``,
``bench/metrics/<metric>.py``, ``bench/models/<model_type>.py``.
"""
