"""On-chip benchmark of the I/O-performance predictor: one harness, driven by
the data in ``BENCHMARK.json`` and the files this package finds by name.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
