"""Chip benchmark of RADS subgraph enumeration (see ``BENCHMARK.json``).

``python3 bench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the TPU it is started on and prints one
JSON line.  Configurations, traffic mixes, per-layer metric readers and
per-pattern reference counters are files of their own under this
directory, found by the names ``BENCHMARK.json`` gives them.
"""
