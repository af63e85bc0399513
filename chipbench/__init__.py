"""Chip benchmark for the LoRDS stack: one cell per run, driven by data.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the chip it is
started on and prints one JSON result line last.  See ``harness.py``.
"""
