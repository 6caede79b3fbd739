"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` has the configuration's tenant deploy it, drive its
traffic mix for ``s`` seconds and check the outputs against its plain
reference, and prints one JSON result line. Configurations, tenants,
traffic mixes and metric readers are files found by name (see
``catalog``).
"""
