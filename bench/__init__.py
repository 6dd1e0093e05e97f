"""On-chip benchmark: one cell (configuration x traffic mix) per run.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Each configuration (``configs/``), traffic mix (``traffic/``), per-layer
metric reader (``metrics/``) and cell's correctness limits (``limits/``)
is a file of its own that the harness finds by its name.
"""
