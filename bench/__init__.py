"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` deploys the cell's configuration, drives its traffic
mix for ``s`` seconds, checks the outputs against a dense float32
reference, and prints one JSON result line. Configurations, traffic
mixes and metric readers are files found by name (see ``catalog``).
"""
