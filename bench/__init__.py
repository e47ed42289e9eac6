"""On-chip benchmark of the compiler's train step.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything that belongs to one
configuration (``configs/``), one traffic mix (``traffic/``), one cell's
limits (``cells/``), one kind of run (``kinds/``) or one per-layer metric
(``metrics/``) sits in files of its own, found by name.
"""
