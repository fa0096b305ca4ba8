"""Chip benchmark of the detection server: one cell per run, driven by
``BENCHMARK.json`` at the repository root (``python3 benchmarks/chip/run.py
--workload <cell> --seed <n> --seconds <s> --trace <0|1>``)."""
