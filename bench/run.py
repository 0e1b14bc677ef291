"""Run one benchmark cell on the machine this is started on.

  python3 bench/run.py --workload bdd.q8 --seed 7 --seconds 10 --trace 0

Prints the run's notes on standard error, the numbers its correctness
check compared (each beside its limit) as the last lines there, and one
JSON result as the last line of standard output.  Exits 2, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.harness import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run(t_start=T_START))
