"""On-chip benchmark of the serving engine and the LoRA trainer.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON line. Everything a
cell needs is found by name: ``configs/<config>.json``,
``traffic/<mix>.json``, ``kinds/<kind>.py``, ``reference/<family>.py`` and
``metrics/<metric>.py``.
"""
