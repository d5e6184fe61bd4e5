"""On-chip serving benchmark: one cell per process, driven by data.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
"""
