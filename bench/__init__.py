"""The chip benchmark of the DEFER serving chain (see BENCHMARK.json).

Entry point: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  Everything that belongs to one
configuration, traffic mix or per-layer metric sits in a file of its own,
found by name (``bench/spec.py``).
"""
