"""On-chip benchmark of the SSSP engine: one command, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``.  Cells, metrics and configurations are listed in
``BENCHMARK.json``; each configuration, traffic mix, driver and metric
reader is a file of its own under this directory, found by name
(:mod:`bench.layout`).
"""
