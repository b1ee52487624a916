"""The chip benchmark of the serving stack: cells, traffic, metrics, checks.

Run one cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout on a TPU host.
"""
