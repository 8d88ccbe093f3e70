"""Chip benchmark of the BaM request path: cells named in BENCHMARK.json.

Run one cell once with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; see ``run.py``.
"""
