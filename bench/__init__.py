"""On-chip benchmark of the SAFL trainer: seconds per federated round.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Configurations, traffic mixes, cells
and per-layer metrics are files under ``bench/configs``, ``bench/traffic``,
``bench/workloads`` and ``bench/metrics``, found by the names in
``BENCHMARK.json``.
"""
