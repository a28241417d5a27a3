"""On-chip benchmark of the training -> checkpoint commit -> serving path.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once; see ``harness.py``.
"""
