"""On-chip benchmark of the dual-core CNN server.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU it is
started on and prints one JSON result line.  Everything that belongs to one
configuration, traffic mix or metric is a file of its own under this
directory, found by the name ``BENCHMARK.json`` gives it.
"""
