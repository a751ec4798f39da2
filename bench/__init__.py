"""On-chip serving benchmark: one cell (configuration x traffic mix) per run.

Run from the repository root:
  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
