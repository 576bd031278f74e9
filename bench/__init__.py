"""Chip benchmark of the scheduler: one cell per run, driven by data.

``python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU this process holds. Every
configuration, traffic mix and metric is found by its name under this
directory; ``PERF.md`` at the root says how to add one.
"""
