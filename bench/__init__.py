"""Chip benchmark of the served b-bit sketch trie: one command runs one
cell (a configuration under a traffic mix) and prints one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it (``spec.py``).
"""
