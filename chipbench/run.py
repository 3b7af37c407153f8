"""``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, the result object the contract
asks for.  Everything else is in ``harness.py``; this file only takes the
process's start time before anything heavy is imported and makes the
checkout importable.
"""
import os
import sys
import time

T_START = time.time()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None, root=ROOT, t_start=None):
    from chipbench import harness
    return harness.main(argv, root, T_START if t_start is None else t_start)


if __name__ == "__main__":
    sys.exit(main())
