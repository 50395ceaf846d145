"""On-chip benchmark: one cell (configuration x traffic) per run.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
"""
