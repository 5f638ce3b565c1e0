"""Chip benchmark of the Byzantine-resilient training step.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything a
cell needs is found by name: its configuration under ``configs/``, its
traffic mix under ``traffic/``, its correctness limits under
``workloads/``, each per-layer metric's reader under ``metrics/`` and the
plain reference of its architecture under ``reference/``.
"""
