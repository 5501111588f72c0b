"""On-chip benchmark of the served engine: one cell per (model config,
traffic mix), driven by the data files beside this package.  Entry point:
``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``."""
